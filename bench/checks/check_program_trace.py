"""Check bench/program_trace.py's readings against answers worked out by
hand, and its profile reduction against brute force.

1. Hand-made tracer records in a window [10, 20] s (the readings of the
   engine's own spans, host clock):
     ttft_wait_p95_ms  turns of conversations 1 and 2 (not 9): self times
                       2.5 (4 less prefill 1.0, export 0.2, import 0.3; the
                       admission 0.3 stays in), 0.6 and 6.5 s, p95 6100 ms
     decode_host_ms    chunks inside the window: 0.4 - 0.25 and 0.2 s,
                       mean 175 ms (one before, one across the close)
     kv_transfer_ms    moves whose import ends in the window: 0.5, 0.3 s,
                       mean 400 ms
     window_compiles   2 of 4 events (both edges count)
2. A hand-made profile, window [0, 100] ns: the decode program's layer
   scan's own ops (a straddling update slice clipped to [0, 3], two slices
   [12, 22] and [40, 45]; the scan's `while` left out) hold 18 of the 63
   decode-busy ns, by kind 10, 5 and 3; the ops named
   `*dynamic-slice_fusion*` hold 15; idle gaps: 7 ns inside
   engine.device_wait, 2 inside engine.prefill after its child closed, 10
   inside engine.kv_export, 10 in the engine loop.
3. Random nested spans and ops on a 2,000 ns window, where the slice share
   and the labelled gaps are worked out again nanosecond by nanosecond.

    python3 bench/checks/check_program_trace.py"""
from __future__ import annotations

import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_trace as pt  # noqa: E402

NS = 1e-9
DEC = "jit_decode_fused_n32_c4096"
L = "jit(decode_fused_n32_c4096)/while/body/layers/while/body/"


def close(a, b) -> bool:
    return a is not None and math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-12)


def say(ok: bool, what: str, got, want) -> bool:
    print(f"{what}: {'ok' if ok else 'WRONG'} (got {got}, want {want})")
    return ok


def rec(i, name, t0, t1, parent=None, cid=None, turn=None, **attrs):
    return SimpleNamespace(id=i, name=name, t0=t0, t1=t1, parent=parent,
                           cid=cid, turn=turn, attrs=attrs)


def hand_made_records() -> bool:
    r = [rec(1, "engine.turn", 11, 15, cid=1, turn=0),
         rec(2, "engine.prefill", 11.5, 12.5, 1, 1, 0),
         rec(3, "engine.kv_export", 12.5, 12.7, 1, 1, 0),
         rec(4, "engine.admission", 12.7, 13.0, 1, 1, 0),
         rec(5, "engine.kv_import", 13.0, 13.3, 1, 1, 0),
         rec(6, "engine.turn", 16, 17, cid=1, turn=1),
         rec(7, "engine.append", 16.5, 16.9, 6, 1, 1),
         rec(8, "engine.turn", 18, 25, cid=2, turn=0),
         rec(9, "engine.prefill", 24, 24.5, 8, 2, 0),
         rec(10, "engine.turn", 12, 13, cid=9, turn=0),
         rec(11, "engine.decode_chunk", 10.5, 10.9),
         rec(12, "engine.device_wait", 10.6, 10.85, 11),
         rec(13, "engine.decode_chunk", 19.5, 20.5),
         rec(14, "engine.device_wait", 19.6, 20.4, 13),
         rec(15, "engine.decode_chunk", 9.0, 9.5),
         rec(16, "engine.decode_chunk", 14, 14.2),
         rec(17, "engine.kv_export", 8, 8.1, cid=5, turn=0),
         rec(18, "engine.kv_import", 9, 9.2, cid=5, turn=0),
         rec(19, "engine.kv_export", 19.8, 19.9, cid=6, turn=0),
         rec(20, "engine.kv_import", 19.9, 20.4, cid=6, turn=0),
         rec(21, "engine.kv_export", 9.8, 9.9, cid=7, turn=0),
         rec(22, "engine.kv_import", 10.1, 10.3, cid=7, turn=0),
         rec(23, "engine.compile", 9.9, 9.9, span=None),
         rec(24, "engine.compile", 12.0, 12.0, 2, span="engine.prefill"),
         rec(25, "engine.compile", 20.0, 20.0, span=None),
         rec(26, "engine.compile", 20.1, 20.1, span=None)]
    w0, w1 = 10, 20
    got = pt.ttft_wait_p95_ms(r, [1, 2])
    ok = say(close(got, 6100.0), "ttft_wait_p95_ms", got, 6100.0)
    got = pt.decode_host_ms_per_chunk(r, w0, w1)
    ok &= say(close(got, 175.0), "decode_host_ms_per_chunk", got, 175.0)
    got = pt.kv_transfer_ms(r, w0, w1)
    ok &= say(close(got, 400.0), "kv_transfer_ms", got, 400.0)
    got = [c.id for c in pt.window_compiles(r, w0, w1)]
    ok &= say(got == [24, 25], "window_compiles", got, [24, 25])
    return ok


def hand_made_profile() -> bool:
    ops = [(DEC, "while.3", "jit(decode_fused_n32_c4096)/while", 10, 60),
           (DEC, "while.4", L[:-6], 12, 50),
           (DEC, "constant_dynamic-slice_fusion.10", L + "dynamic_slice",
            12, 10),
           (DEC, "fusion.1", L + "closed_call/attn/core/dot_general", 22,
            20),
           (DEC, "dynamic-slice_fusion.2", L + "dynamic_slice", 40, 5),
           (DEC, "add_fusion", L + "closed_call/mlp/add", 45, 15),
           (DEC, "fusion.9", None, 65, 3),
           (DEC, "dus", L + "dynamic_update_slice", -5, 8),
           ("jit_prefill_b2048", "dynamic-slice_fusion.1",
            "jit(prefill_b2048)/layers/while/body/dynamic_slice", 80, 4),
           ("jit_prefill_b2048", "fusion.2", "jit(prefill_b2048)/logits",
            86, 4)]
    spans = [("engine.decode_chunk", 2, 70), ("engine.device_wait", 5, 66),
             ("engine.prefill", 78, 15), ("engine.kv_import", 79, 3),
             ("engine.kv_export", 94, 2)]
    red = pt.reduce_profile(ops, spans, (0, 100))
    want = 100.0 * 18 / 63
    ok = say(close(red["decode_layer_slice_pct"], want),
             "decode_layer_slice_pct", red["decode_layer_slice_pct"], want)
    ok &= say(red["decode_ops_unscoped"] == 1, "unscoped decode ops",
              red["decode_ops_unscoped"], 1)
    got = dict(red["layer_scan_own_by_kind"])
    want = {"constant_dynamic-slice_fusion@dynamic_slice": 10 * NS,
            "dynamic-slice_fusion@dynamic_slice": 5 * NS,
            "dus@dynamic_update_slice": 3 * NS}
    ok &= say(set(got) == set(want)
              and all(close(got[k], v) for k, v in want.items()),
              "layer scan's own ops by kind", got, want)
    want = 100.0 * 15 / 63
    ok &= say(close(red["decode_slice_fusion_pct"], want),
              "decode_slice_fusion_pct", red["decode_slice_fusion_pct"], want)
    gaps = dict(red["idle_gaps"])
    want = {"engine.device_wait": 7 * NS, pt.ENGINE_LOOP: 10 * NS,
            "engine.prefill": 2 * NS, "engine.kv_export": 10 * NS}
    ok &= say(set(gaps) == set(want)
              and all(close(gaps[k], v) for k, v in want.items()),
              "idle gaps", gaps, want)
    return ok


def random_nested(rng, lo, hi, depth, names, out):
    """Disjoint spans in [lo, hi], each holding nested ones."""
    t = lo
    while True:
        s = t + int(rng.integers(1, 60))
        e = s + int(rng.integers(5, 200))
        if e >= hi:
            return
        out.append((names[depth % len(names)], s, e - s))
        if depth < 2:
            random_nested(rng, s, e, depth + 1, names, out)
        t = e


def brute_force(ops, spans, w) -> dict:
    w0, w1 = w
    busy = np.zeros(w1 - w0, bool)
    dec = np.zeros(w1 - w0, bool)
    own = np.zeros(w1 - w0, bool)
    for m, n, sc, s, d in ops:
        lo, hi = max(s, w0) - w0, min(s + d, w1) - w0
        if hi <= lo:
            continue
        busy[lo:hi] = True
        if m.startswith(pt.DECODE_MODULE):
            dec[lo:hi] = True
            if sc and pt.layer_scan_own(sc) and not pt.CONTAINER.match(n):
                own[lo:hi] = True
    gaps = {}
    t = 0
    while t < len(busy):
        if busy[t]:
            t += 1
            continue
        u = t
        while u < len(busy) and not busy[u]:
            u += 1
        mid = w0 + (t + u) / 2
        holders = [(s, n) for n, s, d in spans if s <= mid <= s + d]
        label = max(holders)[1] if holders else pt.ENGINE_LOOP
        gaps[label] = gaps.get(label, 0.0) + (u - t) * NS
        t = u
    return {"pct": 100.0 * own.sum() / dec.sum(), "gaps": gaps}


def randomized() -> bool:
    rng = np.random.default_rng(7)
    spans = []
    random_nested(rng, 0, 2000, 0, ["engine.decode_chunk",
                                    "engine.device_wait", "engine.prefill"],
                  spans)
    scopes = [L + "dynamic_slice", L + "closed_call/attn/core/exp",
              L + "closed_call/norm/mul", "jit(d)/while/body/sample/argmax",
              L + "dynamic_update_slice", None]
    ops = []
    t = -20
    while t < 2020:
        d = int(rng.integers(1, 25))
        mod = DEC if rng.random() < 0.8 else "jit_append_b512_c4096"
        ops.append((mod, f"fusion.{len(ops)}", scopes[rng.integers(6)], t,
                    d))
        t += d + (int(rng.integers(1, 30)) if rng.random() < 0.3 else 0)
    red = pt.reduce_profile(ops, spans, (0, 2000))
    want = brute_force(ops, spans, (0, 2000))
    ok = say(close(red["decode_layer_slice_pct"], want["pct"]),
             "random slice share", red["decode_layer_slice_pct"],
             want["pct"])
    gaps = dict(red["idle_gaps"])
    ok &= say(set(gaps) == set(want["gaps"])
              and all(close(gaps[k], v) for k, v in want["gaps"].items()),
              "random idle gaps", gaps, want["gaps"])
    ok &= len(want["gaps"]) >= 3
    return ok


def main() -> int:
    ok = hand_made_records()
    ok &= hand_made_profile()
    ok &= randomized()
    print("check_program_trace:", "OK" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
