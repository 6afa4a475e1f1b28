"""On-chip smoke test of the served path.

Serves full-width qwen3-0.6b (28 layers, d_model 1024, GQA 16/8, head_dim
128, vocab 151936, bf16; random weights from a fixed seed) through
`EngineServer` with the ConServe scheduler — one prefill and two decode
replicas on the one chip — and checks what comes out:

  1. device  — JAX must see a TPU; anything else fails here, naming what it
               found. There is no CPU fallback.
  2. serve   — jnp attention: every conversation completes, each moves its
               KV cache exactly once, and a second pass over the same trace
               on a freshly built engine gives identical per-(cid, turn)
               token streams.
  3. pallas  — the Pallas kernels at qwen3 widths in bf16 against the jnp
               oracles, the full model's prefill logits with Pallas and
               with jnp attention against f32, then the same trace with
               attention_impl="pallas": it must complete, and the compiled
               fused-decode and turn-1 prefill programs must contain the
               kernels. Its streams' agreement with phase 2 is reported,
               not required.

TTFET/TBT come from the engine's logical clock (measured per-call compute
time plus modelled transfers and tool waits); wall seconds and compile
seconds are host-clock readings of this run. The last line of standard
output is a JSON object naming the device, printed only when every phase
passed.

Run from the repository root, on a machine with one TPU chip:

    python chip_smoke.py
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen3-0.6b"
WEIGHT_SEED = 0
N_SLOTS = 4            # per replica
MAX_CTX = 4096         # KV positions per slot
N_CONVERSATIONS = 6
ARRIVAL_RATE = 20.0    # conversations per logical second
TRACE_SEED = 119       # a seed whose draw spans all four turn-1 buckets
KERNEL_TOL = 2e-2      # max abs error of a bf16 kernel vs the f32 oracle
LOGIT_RATIO = 2.0      # Pallas logits may stray from f32 this many times
LOGIT_FLOOR = 1e-2     # as far as jnp logits do, or this far (check_logits)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def require_tpu():
    """Phase 1. Returns (device, count) or exits naming what JAX found."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: FAIL device: no TPU — JAX found "
                 f"{len(devs)} {d.platform} device(s) ({d.device_kind}); "
                 f"this smoke runs only on a TPU chip")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    return d, len(devs)


def smoke_trace():
    """Six seeded agentic conversations: turn 1 of 300-3000 tokens (the
    512-4096 prefill buckets), 2-4 turns, appends of 32-256 tokens, outputs
    of 16-128, tool waits between turns. Checked, so a change to the
    generator cannot shrink the traffic unnoticed."""
    from repro.engine.replica import bucket_len
    from repro.traces import TraceConfig, generate_trace
    tc = TraceConfig(seed=TRACE_SEED, first_input_median=1200,
                     first_input_sigma=0.7, first_input_max=3000,
                     append_median=96, append_sigma=0.6, append_max=256,
                     output_median=48, output_sigma=0.6, output_max=128,
                     mean_turns=3.0, max_turns=4, tool_mean_s=0.2)
    trace = generate_trace(N_CONVERSATIONS, ARRIVAL_RATE, cfg=tc)
    for c in trace:
        t = c.turns
        check(2 <= len(t) <= 4, f"conversation {c.cid}: {len(t)} turns")
        check(300 <= t[0].append_tokens <= 3000,
              f"conversation {c.cid}: turn 1 of {t[0].append_tokens}")
        check(all(32 <= x.append_tokens <= 256 for x in t[1:]),
              f"conversation {c.cid}: append outside 32-256")
        check(all(16 <= x.output_tokens <= 128 for x in t),
              f"conversation {c.cid}: output outside 16-128")
        check(all(x.tool_time_s > 0 for x in t[:-1]),
              f"conversation {c.cid}: a turn without a tool wait")
        peak = sum(x.append_tokens + x.output_tokens for x in t)
        check(peak <= MAX_CTX, f"conversation {c.cid}: peak context {peak}")
    buckets = sorted({bucket_len(c.turns[0].append_tokens) for c in trace})
    check(buckets == [512, 1024, 2048, 4096],
          f"turn-1 buckets {buckets} miss part of 512-4096")
    return trace


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def serve_pass(cfg, attention_impl: str, label: str):
    """Build the deployment, serve the trace once, check completion and
    the one-shot KV transfer, and print the numbers. Returns the engine."""
    from repro.core.metrics import summarize
    from repro.launch.serve import build_engine
    trace = smoke_trace()
    srv = build_engine(cfg, n_slots=N_SLOTS, max_ctx=MAX_CTX,
                       scheduler="conserve", attention_impl=attention_impl,
                       seed=WEIGHT_SEED, strict_accounting=True,
                       record_tokens=True)
    t0 = time.perf_counter()
    recs = srv.serve(trace)
    wall = time.perf_counter() - t0
    by_cid = {r.cid: r for r in recs}
    for c in trace:
        r = by_cid.get(c.cid)
        check(r is not None and r.done and len(r.turns) == len(c.turns),
              f"{label}: conversation {c.cid} did not complete")
        for i, turn in enumerate(c.turns):
            # the prefill's argmax opens the stream, then one token per
            # decode step
            n = len(srv.sampled_tokens.get((c.cid, i), ()))
            check(n == turn.output_tokens + 1,
                  f"{label}: ({c.cid}, {i}) streamed {n} tokens, not "
                  f"{turn.output_tokens} + 1")
    s = summarize(recs)
    check(s["kv_transfers_per_conv"] == 1.0,
          f"{label}: kv_transfers_per_conv {s['kv_transfers_per_conv']}")
    compile_s = sum(r.compile_s for r in srv.replicas.values())
    print(f"{label}: {len(recs)}/{len(trace)} conversations completed, "
          f"kv_transfers_per_conv={s['kv_transfers_per_conv']}")
    print(f"{label}: logical clock: ttfet_gmean={s['ttfet_gmean']}s "
          f"ttfet_p95={s['ttfet_p95']}s last_tbt_gmean={s['last_tbt_gmean']}s "
          f"last_tbt_p95={s['last_tbt_p95']}s e2e_gmean={s['e2e_gmean']}s")
    print(f"{label}: wall_s={wall} compile_s={compile_s} "
          f"peak_bytes_in_use={peak_bytes()}")
    return srv


def phase_serve(cfg):
    """Phase 2: jnp attention, two passes on fresh engines."""
    srv = serve_pass(cfg, "xla", "serve[jnp] pass 1")
    first = dict(srv.sampled_tokens)
    del srv
    gc.collect()
    srv = serve_pass(cfg, "xla", "serve[jnp] pass 2")
    diff = [k for k in first if first[k] != srv.sampled_tokens.get(k)]
    check(not diff and first.keys() == srv.sampled_tokens.keys(),
          f"serve[jnp]: streams differ between passes at {sorted(diff)}")
    print(f"serve[jnp]: {len(first)} (cid, turn) streams identical across "
          f"passes")
    del srv
    gc.collect()
    return first


def check_kernels():
    """The two attention kernels at qwen3 widths in bf16 against the f32
    oracles of kernels/ref.py, through the served-path wrappers."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    def rand(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.6).astype(
            jnp.bfloat16)

    def max_err(got, want):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32))))

    ks = jax.random.split(jax.random.PRNGKey(WEIGHT_SEED), 7)
    B, H, Hkv, D = N_SLOTS, 16, 8, 128
    q = rand(ks[0], (B, H, D))
    k = rand(ks[1], (B, MAX_CTX, Hkv, D))
    v = rand(ks[2], (B, MAX_CTX, Hkv, D))
    lens = jax.random.randint(ks[3], (B,), 1, MAX_CTX + 1)
    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(q.astype(jnp.float32),
                                        k.astype(jnp.float32),
                                        v.astype(jnp.float32), lens)
    err_d = max_err(ops.decode_attention(q, k, v, lens, impl="pallas"), want)
    S = 2048
    qp, kp, vp = (rand(ks[4 + i], (1, S, H, D)) for i in range(3))
    with jax.default_matmul_precision("highest"):
        want = ref.causal_attention_ref(qp.astype(jnp.float32),
                                        kp.astype(jnp.float32),
                                        vp.astype(jnp.float32))
    err_p = max_err(ops.prefill_attention(qp, kp, vp, impl="pallas"), want)
    print(f"pallas: flash_decode (B={B}, H={H}, Hkv={Hkv}, D={D}, "
          f"L={MAX_CTX}) bf16 max_abs_err={err_d}")
    print(f"pallas: flash_prefill (S={S}, H={H}, D={D}) bf16 "
          f"max_abs_err={err_p} (tolerance {KERNEL_TOL})")
    check(err_d < KERNEL_TOL, f"flash_decode max_abs_err {err_d}")
    check(err_p < KERNEL_TOL, f"flash_prefill max_abs_err {err_p}")


def check_logits(cfg):
    """Turn-1 prefill logits of the full model, bf16 weights with jnp and
    with Pallas attention, against the same weights in f32 with jnp
    attention at highest matmul precision. Random bf16 weights through 28
    layers amplify rounding, so greedy streams of the two attention paths
    part within a few tokens and cannot judge the kernels; the logits can.
    The distance is the RMS difference over the f32 logits' spread, and
    Pallas may stray at most LOGIT_RATIO times as far as jnp does, or
    LOGIT_FLOOR where jnp is exact (f32 weights): a wrong kernel lands
    near sqrt(2), uncorrelated with the reference."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model

    params = build_model(cfg).init(jax.random.PRNGKey(WEIGHT_SEED))
    S = 512
    toks = jax.random.randint(jax.random.PRNGKey(S), (1, S), 0,
                              cfg.vocab_size)

    def logits(c, p, impl):
        m = build_model(c)
        out = jax.jit(lambda p, t: m.prefill(p, t, attention_impl=impl)[0])(
            p, toks)
        return out[0, :cfg.vocab_size].astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = logits(cfg.scaled(dtype="float32"),
                      jax.tree_util.tree_map(
                          lambda x: x.astype(jnp.float32), params), "xla")
    dist = {impl: float(jnp.sqrt(jnp.mean((logits(cfg, params, impl)
                                           - want) ** 2)) / jnp.std(want))
            for impl in ("xla", "pallas")}
    print(f"pallas: full-model prefill logits (S={S}) vs f32: "
          f"rms/std jnp={dist['xla']} pallas={dist['pallas']} "
          f"(limit {LOGIT_RATIO}x jnp or {LOGIT_FLOOR})")
    check(dist["pallas"] <= max(LOGIT_RATIO * dist["xla"], LOGIT_FLOOR),
          f"Pallas logits {dist['pallas']} from f32, jnp {dist['xla']}")


def check_kernels_compiled(srv):
    """Every fused-decode program on the decoders and every turn-1 prefill
    program on the prefiller must carry the Pallas kernel. Replicas of one
    signature share their prefill programs, so each is checked once."""
    seen = {"decode": set(), "prefill": set()}
    for rep in srv.replicas.values():
        for key, fn in rep.compiled_programs().items():
            kind = key[0]
            if kind not in seen or id(fn) in seen[kind]:
                continue  # append-prefill reads its prefix through jnp
            check("tpu_custom_call" in fn.as_text(),
                  f"pallas: replica {rep.replica_id} {key} program has no "
                  f"tpu_custom_call")
            seen[kind].add(id(fn))
    seen = {k: len(v) for k, v in seen.items()}
    check(all(seen.values()), f"pallas: compiled programs seen {seen}")
    print(f"pallas: tpu_custom_call in {seen['decode']} fused-decode and "
          f"{seen['prefill']} turn-1 prefill programs")


def phase_pallas(cfg, reference):
    """Phase 3: kernel numerics, then the trace with Pallas attention."""
    check_kernels()
    check_logits(cfg)
    gc.collect()
    srv = serve_pass(cfg, "pallas", "serve[pallas]")
    check_kernels_compiled(srv)
    got = srv.sampled_tokens
    same = sum(a == b for k in reference
               for a, b in zip(reference[k], got.get(k, ())))
    total = sum(len(s) for s in reference.values())
    n_eq = sum(reference[k] == got.get(k) for k in reference)
    print(f"pallas: stream agreement with jnp: {same}/{total} tokens "
          f"({same / total}), {n_eq}/{len(reference)} streams identical")
    del srv
    gc.collect()


def main() -> int:
    t0 = time.perf_counter()
    device, count = require_tpu()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: FAIL setup: the repro package is not under "
                 f"{ROOT / 'src'} ({e})")
    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"config: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}; {N_SLOTS} slots x "
          f"{MAX_CTX} ctx per replica, 1 prefill + 2 decode replicas")
    phase = "serve"
    try:
        reference = phase_serve(cfg)
        phase = "pallas"
        phase_pallas(cfg, reference)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAIL {phase}: {e}")
    print(f"chip_smoke: every phase passed in "
          f"{time.perf_counter() - t0} s (host clock)")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
