"""One fused decode step of a full-width decoder replica on the chip, by the
number of live lanes and their length, and (with --check) the lane kernel
against the XLA lane loop at the same widths.

For each length in --lens and each live-lane count in --lanes, a replica
of --arch (published widths, random weights from --seed, --slots slots of
--max-ctx positions) holds that many lanes at that length and runs
`decode_steps` one step at a time and 32 steps at a time; it prints the
median milliseconds per step of each. The cache holds random KV, so the
read is real work. Run from the root of a checkout on a machine with one
TPU; PYTHONPATH picks the tree measured:

    PYTHONPATH=src python benchmarks/decode_step_chip.py --arch qwen3-0.6b \\
        --slots 6 --max-ctx 4096 --lens 3000,1500 --lanes 1,2,6 --check

Prints one JSON line per (length, lanes); with --setup, one line with the
seconds spent lowering and compiling the decode programs a replica warms,
then lowering and loading them from the persistent compile cache; with
--check, one line with the largest difference of the kernel from the XLA
lane loop over a random layer-stacked cache at these widths, then the
kernel alone, microseconds per layer, at each length and lane count and
at half, one and twice its block.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import numpy as np


def _engine(cfg, params, slots, max_ctx, seed):
    import jax
    from repro.engine import ReplicaEngine
    eng = ReplicaEngine(cfg, params, n_slots=slots, max_ctx=max_ctx)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    eng.kv.caches = jax.tree_util.tree_map(
        lambda l: jax.random.normal(next(keys), l.shape, l.dtype),
        eng.kv.caches)
    return eng


def _step_ms(eng, lanes, length, chunk, reps):
    """Median ms per step of `reps` calls of `chunk` steps with `lanes`
    lanes at `length` (lengths restored before each call)."""
    slots = eng.kv.n_slots
    em = np.arange(slots) < lanes
    nt = np.ones(slots, np.int32)
    out = []
    for i in range(reps + 1):  # the first call compiles
        eng.kv.lengths[:] = np.where(em, length, 0)
        _, dt = eng.decode_steps(nt, em, chunk)
        if i:
            out.append(1e3 * dt / chunk)
    return statistics.median(out)


def _operands(cfg, slots, max_ctx, seed):
    """Random q, new K/V and a layer-stacked bf16 cache at full width."""
    import jax
    H, Hkv, D, NL = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = cfg.jnp_dtype
    return (jax.random.normal(ks[0], (slots, H, D), dt),
            jax.random.normal(ks[1], (slots, Hkv, D), dt),
            jax.random.normal(ks[2], (slots, Hkv, D), dt),
            jax.random.normal(ks[3], (NL, slots, max_ctx, Hkv, D), dt),
            jax.random.normal(ks[4], (NL, slots, max_ctx, Hkv, D), dt))


def _kernel_us(cfg, ops_, lanes, length, block_k, reps=20):
    """Microseconds per layer of the lane kernel alone: one program loops
    it over every layer of the stack, `lanes` lanes live at `length`."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.lane_decode_attention import lane_decode_attention
    q, kn, vn, k, v = ops_
    slots, NL = q.shape[0], k.shape[0]
    lens = jnp.full((slots,), length, jnp.int32)
    live = jnp.arange(slots) < lanes

    @jax.jit
    def run(q, kn, vn, k, v):
        def layer(i, acc):
            return acc + lane_decode_attention(q, kn, vn, k, v, i, lens, live,
                                               block_k=block_k,
                                               interpret=ops._interpret())
        return jax.lax.fori_loop(0, NL, layer,
                                 jnp.zeros(q.shape, jnp.float32))

    jax.block_until_ready(run(q, kn, vn, k, v))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(q, kn, vn, k, v))
        out.append(1e6 * (time.perf_counter() - t0) / NL)
    us = statistics.median(out)
    read = lanes * -(-max(length, 1) // block_k) * block_k
    nbytes = 2 * read * k.shape[3] * k.shape[4] * k.dtype.itemsize
    return {"lanes": lanes, "length": length, "block_k": block_k,
            "us_per_layer": round(us, 2),
            "GB_per_s": round(nbytes / us / 1e3, 1)}


def _check(cfg, ops_, seed):
    """Largest |kernel - XLA lane loop| over every lane, at the last layer
    of a random bf16 cache, every lane live at a random length."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models.attention import lane_decode_attention
    q, kn, vn, k, v = ops_
    slots, NL, max_ctx = q.shape[0], k.shape[0], k.shape[2]
    lens = jax.random.randint(jax.random.PRNGKey(seed + 2), (slots,), 1,
                              max_ctx)
    live = jnp.ones(slots, bool)
    layer = NL - 1
    got = jax.jit(ops.lane_decode_attention)(q, kn, vn, k, v, layer, lens,
                                             live)
    want = jax.jit(lambda *a: lane_decode_attention(cfg, *a, (layer,), lens,
                                                    live))(
        q[:, None], kn[:, None], vn[:, None], k, v)[:, 0]
    want = want.astype(jnp.float32)
    err = jnp.abs(got - want)
    return {"check_max_abs": float(err.max()),
            "check_mean_abs": float(err.mean()),
            "check_rel": float(err.max() / jnp.abs(want).max()),
            "lens": [int(x) for x in lens]}


def _setup_split(eng, passes=2):
    """Seconds spent lowering and compiling (or loading from the persistent
    cache) every fused decode program `warmup_decode` builds by default,
    per pass over the set: the first pass fills the cache, later ones load
    from it, as a warm set-up does."""
    import jax
    import jax.numpy as jnp
    from repro.engine.replica import CTX_BUCKET_MIN, DECODE_CHUNKS
    if getattr(eng, "_lane_kernel", False):
        ctxs = [None]
    else:
        ctxs, c = [], CTX_BUCKET_MIN
        while c < eng.kv.max_ctx:
            ctxs.append(c)
            c *= 2
        ctxs.append(eng.kv.max_ctx)
    spec = lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype)  # noqa
    vec = lambda dt: jax.ShapeDtypeStruct((eng.kv.n_slots,), dt)  # noqa
    args = (jax.tree_util.tree_map(spec, eng.params),
            jax.tree_util.tree_map(spec, eng.kv.caches),
            vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32))
    out = []
    for _ in range(passes):
        lower = comp = 0.0
        for n in DECODE_CHUNKS:
            for c in ctxs:
                t0 = time.perf_counter()
                lo = eng._build_fused(n, c).lower(*args)
                t1 = time.perf_counter()
                lo.compile()
                lower += t1 - t0
                comp += time.perf_counter() - t1
        out.append({"programs": len(DECODE_CHUNKS) * len(ctxs),
                    "lower_s": round(lower, 3),
                    "compile_or_load_s": round(comp, 3)})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--slots", type=int, default=6)
    ap.add_argument("--max-ctx", type=int, default=4096)
    ap.add_argument("--lens", default="3000,1500")
    ap.add_argument("--lanes", default="1,2,6")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--setup", action="store_true",
                    help="also time lowering and compiling (then loading "
                         "from the persistent cache) the decode programs")
    ap.add_argument("--reduced", action="store_true",
                    help="the small float32 reduction (a CPU smoke run)")
    a = ap.parse_args()
    import jax
    from repro.configs import get_config, get_reduced
    from repro.models import build_model
    if a.setup:  # as bench/run.py: every program goes to the cache
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    cfg = (get_reduced if a.reduced else get_config)(a.arch)
    params = build_model(cfg).init(jax.random.PRNGKey(a.seed))
    t0 = time.perf_counter()
    eng = _engine(cfg, params, a.slots, a.max_ctx, a.seed)
    if a.setup:
        print(json.dumps({"arch": a.arch, "setup": _setup_split(eng)}),
              flush=True)
    for length in (int(x) for x in a.lens.split(",")):
        for lanes in (int(x) for x in a.lanes.split(",")):
            print(json.dumps({
                "arch": a.arch, "device": dev.device_kind,
                "length": length, "lanes": lanes,
                "ms_per_step_chunk1": _step_ms(eng, lanes, length, 1,
                                               a.reps),
                "ms_per_step_chunk32": _step_ms(eng, lanes, length, 32,
                                                max(2, a.reps // 4)),
            }), flush=True)
    print(json.dumps({"arch": a.arch, "decode_programs": len(eng._fused),
                      "compile_s": round(eng.compile_s, 3),
                      "wall_s": round(time.perf_counter() - t0, 3)}),
          flush=True)
    if a.check:
        import jax.numpy as jnp
        from repro.kernels.lane_decode_attention import lane_block_k
        del eng
        ops_ = _operands(cfg, a.slots, a.max_ctx, a.seed)
        print(json.dumps({"arch": a.arch, **_check(cfg, ops_, a.seed)}),
              flush=True)
        bk = lane_block_k(a.max_ctx, cfg.n_kv_heads, cfg.head_dim,
                          jnp.dtype(cfg.jnp_dtype).itemsize)
        for length in (int(x) for x in a.lens.split(",")):
            for lanes in [0] + [int(x) for x in a.lanes.split(",")]:
                for b in (x for x in (bk // 2, bk, 2 * bk)
                          if a.max_ctx % x == 0):
                    print(json.dumps({"arch": a.arch, "kernel": _kernel_us(
                        cfg, ops_, lanes, length, b)}), flush=True)


if __name__ == "__main__":
    main()
