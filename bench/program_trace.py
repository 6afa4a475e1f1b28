"""The engine's own trace, read beside one run of a cell.

    python3 bench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

runs the cell exactly as bench/run.py does (same arguments, same checks,
the same result line last) with two additions, made from outside run.py:
the program's tracer (`EngineServer.tracer`, repro/core/tracing.py) is
turned on where run.py instruments a traced run, just before the traffic
is driven; and on a traced run the profile is read for the program's own
spans and scopes before run.py removes it. With --trace 0 nothing is
profiled, so two sets of untraced runs, with and without this wrapper,
give what the tracer costs when it is on.

What it reads, and prints in one `program_trace {...}` line before the
result line (times in ms unless named):

  ttft_wait_p95_ms          p95, over every turn of the conversations that
                            arrived in the window (the turns `ttft_p95_s`
                            reads), of the self time of `engine.turn`: its
                            length less its prefill, append and KV
                            export/import children. The wait behind other
                            work on the loop, plus admission parking
  decode_host_ms_per_chunk  mean, over the `engine.decode_chunk` spans
                            inside the window, of their time outside their
                            `engine.device_wait` child: input staging,
                            dispatch, readback
  kv_transfer_ms            mean, over the one-shot transfers whose import
                            ended inside the window, of the export and
                            import span time of that (cid, turn)
  window_compiles           `engine.compile` events inside the window, each
                            named with the span it fell in
  decode_layer_slice_pct    (traced runs) the union of the device intervals
                            of decode-program ops that are the layer scan's
                            own (scope under `layers`, outside `attn`,
                            `mlp` and `norm`; control flow left out) over
                            the union of all decode-program op intervals
  idle_gaps                 (traced runs) the traced window's idle device
                            time, each gap labelled by the innermost
                            `engine.*` span open at its midpoint, or
                            "engine loop"

and two cross-checks: the p95 of the window turns' `engine.turn` lengths
against the run's host-clock `ttft_p95_s`, and the slice share against the
share the benchmark's breakdown gives its `dynamic-slice` fusions.

The window is run.py's: host `perf_counter` readings from the window's
opening (`t_open`) to its close (`c_close["wall"]`). On the profile, the
window is the `bench.window#0` annotation. A device op's scope is the
op_name of its own event stats where the profile carries it, else of the
compiled program's HLO metadata, keyed by module name and instruction
name. bench/checks/check_program_trace.py checks the readings on hand-made
records and a hand-made profile."""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

TURN = "engine.turn"
CHUNK = "engine.decode_chunk"
WAIT = "engine.device_wait"
KV = ("engine.kv_export", "engine.kv_import")
WORK = ("engine.prefill", "engine.append") + KV
COMPILE = "engine.compile"
DECODE_MODULE = "jit_decode_fused"
CHILD_SCOPES = ("attn", "mlp", "norm")
CONTAINER = re.compile(r"^(while|call|conditional)(\.\d+)?$")
NUMBERING = re.compile(r"(\.clone)?(\.\d+)*$")
SLICE_FUSION = "dynamic-slice_fusion"
WINDOW_SPAN = "bench.window#0"
ENGINE_LOOP = "engine loop"
OP_NAME_STATS = ("tf_op", "op_name", "long_name")


def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def mean(values) -> Optional[float]:
    return float(np.mean(values)) if len(values) else None


# ----- the tracer's records (host clock) --------------------------------------
def kids_of(records) -> Dict[int, list]:
    out = defaultdict(list)
    for r in records:
        if r.parent is not None:
            out[r.parent].append(r)
    return out


def turn_parts(records, cids) -> List[dict]:
    """Per turn of the conversations `cids`: its length, and the time of its
    children by name (seconds); `self` is what its work does not cover."""
    cids = set(cids)
    kids = kids_of(records)
    rows = []
    for r in records:
        if r.name != TURN or r.cid not in cids:
            continue
        parts = defaultdict(float)
        for k in kids[r.id]:
            if k.t1 is not None and k.t1 > k.t0:
                parts[k.name] += k.t1 - k.t0
        work = sum(parts[n] for n in WORK)
        rows.append({"cid": r.cid, "turn": r.turn, "dur": r.t1 - r.t0,
                     "self": r.t1 - r.t0 - work, **parts})
    return rows


def ttft_wait_p95_ms(records, cids) -> Optional[float]:
    v = pct([t["self"] for t in turn_parts(records, cids)], 95)
    return None if v is None else 1e3 * v


def decode_host_ms_per_chunk(records, w0: float, w1: float
                             ) -> Optional[float]:
    kids = kids_of(records)
    host = [(r.t1 - r.t0) - sum(k.t1 - k.t0 for k in kids[r.id]
                                if k.name == WAIT)
            for r in records if r.name == CHUNK and w0 <= r.t0
            and r.t1 <= w1]
    v = mean(host)
    return None if v is None else 1e3 * v


def kv_transfer_ms(records, w0: float, w1: float) -> Optional[float]:
    """Mean over the (cid, turn) moves whose import ended in the window of
    their export plus import time."""
    per = defaultdict(float)
    ended = set()
    for r in records:
        if r.name in KV:
            per[(r.cid, r.turn)] += r.t1 - r.t0
            if r.name == KV[1] and w0 <= r.t1 <= w1:
                ended.add((r.cid, r.turn))
    v = mean([per[k] for k in ended])
    return None if v is None else 1e3 * v


def window_compiles(records, w0: float, w1: float) -> list:
    return [r for r in records if r.name == COMPILE and w0 <= r.t0 <= w1]


# ----- the profile (device trace clock) ----------------------------------------
def union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def layer_scan_own(scope: str) -> bool:
    """Whether an op's scope is the layer scan's own work: under `layers`,
    outside every per-layer child scope."""
    parts = scope.split("/")
    if "layers" not in parts:
        return False
    tail = parts[len(parts) - parts[::-1].index("layers"):]
    return not any(p in CHILD_SCOPES for p in tail)


def nesting(spans) -> List[int]:
    """Index of each span's enclosing span (-1 at the top), for spans
    sorted by start. The engine's lexical spans come from one thread, so
    two of them either nest or do not overlap."""
    parent, stack = [], []
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] < s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return parent


def innermost(spans, starts, parent, t) -> Optional[str]:
    """Name of the innermost span that holds instant t: the last span that
    starts at or before t, or the nearest of its enclosing spans that has
    not ended by t."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and spans[i][1] < t:
        i = parent[i]
    return spans[i][2] if i >= 0 else None


def reduce_profile(ops, spans, window) -> dict:
    """ops: (module, instruction, scope or None, start_ns, dur_ns) per device
    op; spans: (name, start_ns, dur_ns) per `engine.*` host annotation;
    window: (start_ns, end_ns). Returns the decode layer-slice share, the
    seconds behind it by op kind (instruction name without its numbering,
    @ the last scope component), the share of the decode ops named
    `*dynamic-slice_fusion*` (what the benchmark's breakdown can name), and
    the idle gaps by innermost engine span."""
    w0, w1 = window
    clip = [(m, n, sc, max(s, w0), min(s + d, w1)) for m, n, sc, s, d in ops
            if s + d > w0 and s < w1]
    dec = [o for o in clip if o[0].startswith(DECODE_MODULE)]
    own = [(n, sc, s, e) for _, n, sc, s, e in dec
           if sc and layer_scan_own(sc) and not CONTAINER.match(n)]

    def share(iv):
        return sum(e - s for s, e in union(iv))

    dec_s = share([(s, e) for _, _, _, s, e in dec])
    own_s = share([(s, e) for _, _, s, e in own])
    fused_s = share([(s, e) for _, n, _, s, e in dec if SLICE_FUSION in n])
    kinds: Dict[str, float] = defaultdict(float)
    for n, sc, s, e in own:
        kinds[f"{NUMBERING.sub('', n)}@{sc.rsplit('/', 1)[-1]}"] += \
            (e - s) * 1e-9

    sp = sorted(((s, s + d, n) for n, s, d in spans),
                key=lambda x: (x[0], -x[1]))
    starts = [s for s, _, _ in sp]
    parent = nesting(sp)
    busy = union([(s, e) for _, _, _, s, e in clip])
    gaps: Dict[str, float] = defaultdict(float)
    edges = [(w0, w0)] + busy + [(w1, w1)]
    for (_, e), (s, _) in zip(edges, edges[1:]):
        if s > e:
            label = innermost(sp, starts, parent, (s + e) / 2)
            gaps[label or ENGINE_LOOP] += (s - e) * 1e-9
    return {"decode_layer_slice_pct":
            100.0 * own_s / dec_s if dec_s > 0 else None,
            "decode_busy_s": dec_s * 1e-9, "layer_scan_own_s": own_s * 1e-9,
            "layer_scan_own_by_kind": sorted(
                ([k, v] for k, v in kinds.items()), key=lambda kv: -kv[1])[:8],
            "decode_slice_fusion_pct":
            100.0 * fused_s / dec_s if dec_s > 0 else None,
            "decode_ops_unscoped": sum(1 for o in dec if not o[2]),
            "decode_unscoped_s": share([(s, e) for _, _, sc, s, e in dec
                                        if not sc]) * 1e-9,
            "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                key=lambda kv: -kv[1])}


def hlo_scopes(reps, modules) -> Dict[Tuple[str, str], str]:
    """(module, instruction) -> op_name from the compiled HLO of the fused
    decode programs of `reps` whose module is in `modules`."""
    out: Dict[Tuple[str, str], str] = {}
    done = set()
    pat = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"')
    for r in reps:
        for key, fn in r.compiled_programs().items():
            mod = f"{DECODE_MODULE}_n{key[1]}_c{key[2]}"
            if key[0] != "decode" or mod not in modules or mod in done:
                continue
            done.add(mod)
            for line in fn.as_text().splitlines():
                m = pat.match(line)
                if m:
                    out[(mod, m.group(1))] = m.group(2)
    return out


def load_profile(log_dir: str, reps) -> dict:
    """Device ops with their module and scope, the engine spans, and the
    traced window, from the first .xplane.pb under `log_dir`."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = ProfileData.from_file(path)
    raw, mods, spans, window, stat_keys = [], [], [], None, None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(e.start_ns, e.start_ns + e.duration_ns,
                              e.name.split("(")[0]) for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        st = dict(e.stats)
                        if stat_keys is None:
                            stat_keys = sorted(st)
                        raw.append((e.name, e.start_ns, e.duration_ns, st))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    mods.sort()
    mstarts = [s for s, _, _ in mods]

    def module_of(t, st):
        if st.get("hlo_module"):
            return str(st["hlo_module"])
        i = bisect.bisect_right(mstarts, t) - 1
        return mods[i][2] if i >= 0 and mods[i][1] >= t else ""

    ops, via = [], defaultdict(int)
    for name, s, d, st in raw:
        inst = name.split(" = ", 1)[0].lstrip("%")
        scope = next((str(st[k]) for k in OP_NAME_STATS
                      if "/" in str(st.get(k, ""))), None)
        via["stats" if scope else "hlo"] += 1
        ops.append([module_of(s, st), inst, scope, s, d])
    seen = {o[0] for o in ops if o[2] is None and
            o[0].startswith(DECODE_MODULE)}
    hlo = hlo_scopes(reps, seen) if seen else {}
    for o in ops:
        if o[2] is None:
            o[2] = hlo.get((o[0], o[1]))
    if window is None and ops:
        window = (min(o[3] for o in ops), max(o[3] + o[4] for o in ops))
    return {"ops": [tuple(o) for o in ops], "spans": spans,
            "window": window, "op_stat_keys": stat_keys,
            "modules": sorted({m for _, _, m in mods})[:8],
            "scope_from": dict(via)}


# ----- one run --------------------------------------------------------------------
def readings(r) -> dict:
    """Everything above, for a finished bench.run.Run."""
    recs = list(r.program_tracer.records)
    w0, w1 = r.t_open, r.c_close["wall"]
    cids = r.in_window
    rows = turn_parts(recs, cids)
    comp = window_compiles(recs, w0, w1)
    out = {"ttft_wait_p95_ms": ttft_wait_p95_ms(recs, cids),
           "decode_host_ms_per_chunk": decode_host_ms_per_chunk(recs, w0, w1),
           "kv_transfer_ms": kv_transfer_ms(recs, w0, w1),
           "window_compiles": len(comp),
           "bench_window_compiles": r.obs.window_compiles,
           "compiles_by_span": sorted(
               [c.attrs.get("span"), c.attrs.get("program")] for c in comp),
           "turns": len(rows),
           "records": len(recs)}
    for part in ("dur", "self", "engine.admission") + WORK:
        vals = [t.get(part, 0.0) for t in rows]
        out[f"turn_{part}_ms"] = {"mean": None if not vals else
                                  1e3 * float(np.mean(vals)),
                                  "p95": None if not vals else
                                  1e3 * pct(vals, 95)}
    chunks = [x for x in recs if x.name == CHUNK and w0 <= x.t0
              and x.t1 <= w1]
    kids = kids_of(recs)
    out["decode_chunks"] = len(chunks)
    out["device_wait_ms_per_chunk"] = mean(
        [1e3 * sum(k.t1 - k.t0 for k in kids[c.id] if k.name == WAIT)
         for c in chunks])
    ttft = [t.first - t.runnable for t in
            (r.obs.turn(c, i) for c in cids
             for i in range(len(r.obs.convs[c])))
            if t.first is not None and t.runnable is not None]
    turn_p95 = pct([t["dur"] for t in rows], 95)
    ttft_p95 = pct(ttft, 95)
    out["cross_turn_p95_s"] = turn_p95
    out["cross_ttft_p95_s"] = ttft_p95
    out["cross_turn_over_ttft"] = (turn_p95 / ttft_p95 if turn_p95 and
                                   ttft_p95 else None)
    prof = getattr(r, "program_profile", None)
    if prof:
        red = reduce_profile(prof["ops"], prof["spans"], prof["window"])
        out.update(red)
        out.update({k: prof[k] for k in ("op_stat_keys", "modules",
                                         "scope_from")})
        out["engine_spans_in_profile"] = len(prof["spans"])
        tr = r.trace_red
        fused = sum(v for k, v in tr["device_ops"]
                    if k.startswith("decode:") and "dynamic-slice_fusion"
                    in k)
        dec = tr["kind_device_s"].get("decode", 0.0)
        out["cross_breakdown_slice_pct"] = (100.0 * fused / dec if dec
                                            else None)
    return out


def main(argv=None) -> int:
    import run as bench
    args = bench.parse(argv)
    cpu = os.environ.get("JAX_PLATFORMS") == "cpu"  # rehearsal: reduced
    subscribe, reduce_trace = bench.Run.subscribe, bench.Run.reduce_trace

    def subscribe_on(self):
        subscribe(self)
        self.program_tracer = self.srv.tracer.enable()

    def reduce_with_program(self):
        t0 = time.perf_counter()
        self.program_profile = load_profile(self.trace_dir, self.reps)
        self.say(f"program trace: profile read in "
                 f"{time.perf_counter() - t0:.3f} s")
        reduce_trace(self)

    bench.Run.subscribe = subscribe_on
    bench.Run.reduce_trace = reduce_with_program
    try:
        r = bench.run(args, allow_cpu=cpu, reduced=cpu)
    except bench.BenchError as e:
        sys.exit(f"bench: {e}")
    print("program_trace " + json.dumps(readings(r)), flush=True)
    bench.emit(r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
