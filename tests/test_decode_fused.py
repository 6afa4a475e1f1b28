"""Decode-tail rebuild tests: the fused donated in-place decode step vs the
retained per-token reference path, the multi-token RAGGED scan loop
(per-slot remaining, mid-chunk freezes), the in-place live-lane KV read
against the batched read it replaced, the length-trimmed flash-decode
grid, ctx-trimmed model decode, and end-to-end EngineServer equivalence
between decode modes including staggered-finish agentic traces."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import make_scheduler
from repro.core.conversation import Conversation, Turn
from repro.core.tracing import Tracer
from repro.engine import EngineServer, ReplicaEngine
from repro.engine.replica import DECODE_CHUNKS
from repro.engine.kvcache import SlotKVCache, fold_decode_step
from repro.kernels import lane_decode_attention as lane_kernel
from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode_attention
from repro.models import attention, build_model
from repro.traces import TraceConfig, generate_trace


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prefill_two(cfg, params, n_slots=4, max_ctx=256):
    eng = ReplicaEngine(cfg, params, n_slots=n_slots, max_ctx=max_ctx)
    s0, s1 = eng.kv.acquire(), eng.kv.acquire()
    t0, _ = eng.prefill_conversation(s0, np.arange(11, 48, dtype=np.int32))
    t1, _ = eng.prefill_conversation(s1, np.arange(100, 111, dtype=np.int32))
    nt = np.zeros(n_slots, np.int32)
    em = np.zeros(n_slots, bool)
    nt[s0], nt[s1] = int(t0), int(t1)
    em[s0] = em[s1] = True
    return eng, (s0, s1), nt, em


# --------------------------------------------------------------------------- #
# fused in-place decode vs the retained per-token reference path
# --------------------------------------------------------------------------- #
def test_fused_decode_matches_reference_tokens_and_cache(qwen):
    cfg, model, params = qwen
    ref_eng, (s0, s1), nt_r, em = _prefill_two(cfg, params)
    fus_eng, _, nt_f, _ = _prefill_two(cfg, params)
    np.testing.assert_array_equal(nt_r, nt_f)

    ref_toks = {s0: [], s1: []}
    for _ in range(6):
        sampled, _ = ref_eng.decode_step_all_reference(nt_r, em)
        for s in (s0, s1):
            ref_toks[s].append(int(sampled[s]))
            nt_r[s] = int(sampled[s])

    seq, _ = fus_eng.decode_steps(nt_f, em, 6)
    fus_toks = {s: [int(t) for t in seq[:, s]] for s in (s0, s1)}
    assert fus_toks == ref_toks

    # donated in-place scatter must leave byte-identical cache state
    for a, b in zip(jax.tree_util.tree_leaves(ref_eng.kv.caches),
                    jax.tree_util.tree_leaves(fus_eng.kv.caches)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
    np.testing.assert_array_equal(ref_eng.kv.lengths, fus_eng.kv.lengths)


def test_multi_step_equals_repeated_single_step(qwen):
    cfg, model, params = qwen
    a, (s0, s1), nt_a, em = _prefill_two(cfg, params)
    b, _, nt_b, _ = _prefill_two(cfg, params)

    seq_multi, _ = a.decode_steps(nt_a, em, 5)
    singles = []
    for _ in range(5):
        seq, _ = b.decode_steps(nt_b, em, 1)
        singles.append(seq[0])
        for s in (s0, s1):
            nt_b[s] = int(seq[0, s])
    for i in range(5):
        for s in (s0, s1):
            assert int(seq_multi[i, s]) == int(singles[i][s])


def test_decode_chunk_does_not_advance_inactive_slots(qwen):
    cfg, model, params = qwen
    eng, (s0, s1), nt, em = _prefill_two(cfg, params)
    em[s1] = False  # only s0 decodes
    len1_before = int(eng.kv.lengths[s1])
    cache_row = np.asarray(
        jax.tree_util.tree_leaves(eng.kv.export_slot(s1)["caches"])[0])
    eng.decode_steps(nt, em, 4)
    assert int(eng.kv.lengths[s1]) == len1_before
    cache_row_after = np.asarray(
        jax.tree_util.tree_leaves(eng.kv.export_slot(s1)["caches"])[0])
    np.testing.assert_array_equal(cache_row, cache_row_after)


# --------------------------------------------------------------------------- #
# ragged per-slot chunks: mid-scan freezes, overflow guard, warmup
# --------------------------------------------------------------------------- #
def test_ragged_chunk_matches_per_token_reference_replay(qwen):
    """decode_steps with a per-slot remaining vector must be token- and
    cache-exact against the per-token reference path replayed with the
    same shrinking live mask (slot freezes at step remaining[s])."""
    cfg, model, params = qwen
    fus, (s0, s1), nt_f, em = _prefill_two(cfg, params)
    ref_eng, _, nt_r, _ = _prefill_two(cfg, params)

    rem = np.zeros(fus.kv.n_slots, np.int32)
    rem[s0], rem[s1] = 3, 7
    seq, _ = fus.decode_steps(nt_f, em, rem)
    assert seq.shape[0] == 7  # rows = max(remaining), not the 8-bucket

    ref_toks = {s0: [], s1: []}
    for i in range(7):
        mask = em & (i < rem)
        sampled, _ = ref_eng.decode_step_all_reference(nt_r, mask)
        for s in np.flatnonzero(mask):
            ref_toks[s].append(int(sampled[s]))
            nt_r[s] = int(sampled[s])
    fus_toks = {s: [int(t) for t in seq[: rem[s], s]] for s in (s0, s1)}
    assert fus_toks == ref_toks

    # the short slot advanced by exactly its own remaining, and its cache
    # row is byte-identical to the reference replay's
    np.testing.assert_array_equal(fus.kv.lengths, ref_eng.kv.lengths)
    for a, b in zip(jax.tree_util.tree_leaves(fus.kv.caches),
                    jax.tree_util.tree_leaves(ref_eng.kv.caches)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_ragged_chunk_equals_scalar_when_uniform(qwen):
    """A uniform remaining vector must reproduce the scalar-n contract."""
    cfg, model, params = qwen
    a, (s0, s1), nt_a, em = _prefill_two(cfg, params)
    b, _, nt_b, _ = _prefill_two(cfg, params)
    rem = np.where(em, 5, 0).astype(np.int32)
    seq_v, _ = a.decode_steps(nt_a, em, rem)
    seq_s, _ = b.decode_steps(nt_b, em, 5)
    np.testing.assert_array_equal(seq_v[:, [s0, s1]], seq_s[:, [s0, s1]])
    np.testing.assert_array_equal(a.kv.lengths, b.kv.lengths)


def test_decode_steps_overflow_names_offending_slot(qwen):
    """The per-slot overflow guard must name the slot that would overflow,
    not just report the batch max."""
    cfg, model, params = qwen
    eng, (s0, s1), nt, em = _prefill_two(cfg, params, max_ctx=64)
    eng.kv.lengths[s1] = 62  # 2 tokens of room left
    rem = np.zeros(eng.kv.n_slots, np.int32)
    rem[s0], rem[s1] = 4, 4
    with pytest.raises(RuntimeError, match=rf"slot {s1} at length 62"):
        eng.decode_steps(nt, em, rem)
    # the same call is fine once clamped to the slot's room
    rem[s1] = 2
    eng.decode_steps(nt, em, rem)


def test_decode_steps_rejects_nonpositive_remaining_on_emitting_slot(qwen):
    cfg, model, params = qwen
    eng, (s0, s1), nt, em = _prefill_two(cfg, params)
    rem = np.zeros(eng.kv.n_slots, np.int32)
    rem[s0] = 3  # s1 emits but has remaining 0
    with pytest.raises(ValueError, match=rf"slot\(s\) \[{s1}\]"):
        eng.decode_steps(nt, em, rem)


def test_decode_steps_rejects_over_bucket_remaining(qwen):
    """A per-slot remaining above the largest compiled chunk must raise —
    silently clamping would desync the caller's bookkeeping from
    kv.lengths (the scalar path keeps its historic clamp)."""
    cfg, model, params = qwen
    eng, (s0, s1), nt, em = _prefill_two(cfg, params)
    rem = np.zeros(eng.kv.n_slots, np.int32)
    rem[s0], rem[s1] = 3, 40
    with pytest.raises(ValueError, match=rf"slot {s1} remaining 40"):
        eng.decode_steps(nt, em, rem)


def test_warmup_precompiles_and_separates_compile_time(qwen):
    """warmup_decode pre-builds (chunk, ctx) buckets; compile time lands in
    compile_s and never in the measured decode dt."""
    cfg, model, params = qwen
    eng = ReplicaEngine(cfg, params, n_slots=4, max_ctx=128)
    spent = eng.warmup_decode(chunks=(1, 4), ctx_limits=(64,))
    assert spent > 0
    assert (1, 64) in eng._fused and (4, 64) in eng._fused
    assert eng.compile_s == pytest.approx(spent)

    s0 = eng.kv.acquire()
    t0, _ = eng.prefill_conversation(s0, np.arange(7, 30, dtype=np.int32))
    nt = np.zeros(4, np.int32)
    em = np.zeros(4, bool)
    nt[s0], em[s0] = int(t0), True
    before = eng.compile_s
    _, dt = eng.decode_steps(nt, em, 4)  # hits the pre-warmed (4, 64) bucket
    assert eng.compile_s == before  # no compile charged on a warm bucket
    # a cold bucket compiles into compile_s, and the reported dt stays in
    # the same regime as the warm call (compile is NOT in dt)
    _, dt_cold = eng.decode_steps(nt, em, 2)
    assert eng.compile_s > before
    assert dt_cold < 100 * max(dt, 1e-4)


# --------------------------------------------------------------------------- #
# in-place live-lane KV read vs the batched read + post-scan fold
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("arch,kv_heads,ctx_limit", [
    ("qwen3-0.6b", 4, 64),     # G = 1, ctx bucket below max_ctx
    ("qwen3-0.6b", 2, 128),    # G = 2, ctx bucket at max_ctx
    ("qwen3-0.6b", 2, 64),     # G = 2, ctx bucket below max_ctx
    ("gemma3-12b", 4, 128),    # sliding-window layers beside a global one
    ("recurrentgemma-9b", 1, 128),  # recurrent states beside a window, G = 4
    ("deepseek-v2-lite-16b", 4, 64),  # MLA keeps its batched trimmed read
    ("olmo-1b", 4, 128),       # MHA (G = 1), non-parametric LayerNorm,
                               # ctx bucket at max_ctx
])
@pytest.mark.parametrize("seed", [0, 1])
def test_decode_lanes_matches_batched_read(arch, kv_heads, ctx_limit, seed):
    """`Model.decode_lanes` (rows written inside the layer scan, attention
    over the live lanes only) against `decode_step` + `fold_decode_step`
    (every lane's slab read, rows folded after the scan) over a ragged
    chunk: a random live mask, random per-lane remaining, a lane whose
    length + n_steps equals ctx_limit exactly. Live logits agree to float32
    rounding (1e-4, as the ctx-trim test below: the sums run in another
    order through every layer), greedy tokens are equal, caches agree, and
    every position a lane did not write keeps its bytes."""
    cfg = dataclasses.replace(get_reduced(arch), n_kv_heads=kv_heads)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    B, max_ctx, n_steps = 5, 128, 6
    kv = SlotKVCache(model, B, max_ctx)
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(100 + seed), 64))
    caches0 = jax.tree_util.tree_map(
        lambda l: jax.random.normal(next(keys), l.shape).astype(l.dtype),
        kv.caches)
    emit = rng.random(B) < 0.6
    emit[0], emit[-1] = True, False
    rem = np.where(emit, rng.integers(1, n_steps + 1, B), 0)
    rem[0] = n_steps
    lens0 = rng.integers(1, ctx_limit - n_steps + 1, B)
    lens0[0] = ctx_limit - n_steps  # its last step writes position ctx-1

    old_step = jax.jit(
        lambda c, t, n, live: (lambda lg, up: (lg, fold_decode_step(
            c, up, n, live, kv._grouped, kv._growing)))(
            *model.decode_step(params, t, c, n, kv_lens=n,
                               ctx_limit=ctx_limit)))
    new_step = jax.jit(lambda c, t, n, live: model.decode_lanes(
        params, t, c, n, live, ctx_limit=ctx_limit))
    c_old = c_new = caches0
    lens = jnp.asarray(lens0, jnp.int32)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    for i in range(n_steps):
        live = jnp.asarray(emit & (i < rem))
        lg_old, c_old = old_step(c_old, tok, lens, live)
        lg_new, c_new = new_step(c_new, tok, lens, live)
        on = np.flatnonzero(np.asarray(live))
        a = np.asarray(lg_old)[on, :cfg.vocab_size]
        b = np.asarray(lg_new)[on, :cfg.vocab_size]
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(b.argmax(-1), a.argmax(-1))
        tok = jnp.where(live, jnp.argmax(lg_old[:, :cfg.vocab_size], -1),
                        tok).astype(jnp.int32)
        lens = lens + live.astype(jnp.int32)

    written = np.zeros((B, max_ctx), bool)
    for s_ in range(B):
        written[s_, lens0[s_]:lens0[s_] + rem[s_]] = True
    flat = jax.tree_util.tree_flatten_with_path
    for (path, l0), l_old, l_new, g, gr in zip(
            flat(caches0)[0], jax.tree_util.tree_leaves(c_old),
            jax.tree_util.tree_leaves(c_new),
            jax.tree_util.tree_leaves(kv._grouped),
            jax.tree_util.tree_leaves(kv._growing)):
        l0, l_old, l_new = map(np.asarray, (l0, l_old, l_new))
        np.testing.assert_allclose(l_new, l_old, rtol=1e-5, atol=1e-5,
                                   err_msg=str(path))
        if g:  # one layer's view: (B, L, ...)
            l0, l_new = l0[0], l_new[0]
        if gr:  # positions no lane wrote keep their bytes
            keep = ~written[:, :l0.shape[1]]
            np.testing.assert_array_equal(l_new[keep], l0[keep],
                                          err_msg=str(path))
        else:  # fixed states of lanes that never emitted keep theirs
            np.testing.assert_array_equal(l_new[~emit], l0[~emit],
                                          err_msg=str(path))


def test_decode_chunk_counts_kv_read(qwen):
    """`engine.decode_chunk` reports `kv_read`, the KV positions the chunk
    read per layer: the sum over steps of the live lanes times the ctx
    bucket, on a ragged chunk."""
    cfg, model, params = qwen
    eng, (s0, s1), nt, em = _prefill_two(cfg, params)
    tr = eng.tracer = Tracer(enabled=True)
    rem = np.zeros(eng.kv.n_slots, np.int32)
    rem[s0], rem[s1] = 3, 7
    eng.decode_steps(nt, em, rem)
    chunk = next(r for r in tr.records if r.name == "engine.decode_chunk")
    live = int(sum((em & (i < rem)).sum() for i in range(8)))  # 8 steps
    assert live == 10
    assert chunk.attrs["ctx"] == 64
    assert chunk.attrs["kv_read"] == live * chunk.attrs["ctx"]


# --------------------------------------------------------------------------- #
# the length-bounded Pallas lane read, forced on by the platform check
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def qwen_k128():
    """The reduced qwen at a head width the lane kernel tiles (128) and two
    query heads per KV head."""
    cfg = dataclasses.replace(get_reduced("qwen3-0.6b"), head_dim=128,
                              n_heads=8)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _kernel_engine(monkeypatch, cfg, params, **kw):
    """A two-slot-prefilled replica whose decode takes the lane kernel
    (interpreted on the CPU): the platform check says TPU."""
    monkeypatch.setattr(attention, "_kernel_platform", lambda: True)
    out = _prefill_two(cfg, params, **kw)
    assert out[0]._lane_kernel
    return out


def test_lane_kernel_decode_matches_xla_path(monkeypatch, qwen_k128):
    """A ragged fused chunk through the lane kernel gives the XLA lane
    loop's tokens and lengths; written rows agree to f32 rounding, and every
    cache position outside the written rows keeps its bytes."""
    cfg, params = qwen_k128
    xla, (s0, s1), nt_x, em = _prefill_two(cfg, params)
    assert not xla._lane_kernel
    rem = np.zeros(xla.kv.n_slots, np.int32)
    rem[s0], rem[s1] = 3, 7
    seq_x, _ = xla.decode_steps(nt_x, em, rem)

    ker, _, nt_k, _ = _kernel_engine(monkeypatch, cfg, params)
    lens0 = ker.kv.lengths.copy()
    before = jax.tree_util.tree_map(np.asarray, ker.kv.caches)
    seq_k, _ = ker.decode_steps(nt_k, em, rem)
    assert ker._fused and all(c is None for _, c in ker._fused)
    for s_ in (s0, s1):
        np.testing.assert_array_equal(seq_k[:rem[s_], s_],
                                      seq_x[:rem[s_], s_])
    np.testing.assert_array_equal(ker.kv.lengths, xla.kv.lengths)
    written = np.zeros((ker.kv.n_slots, ker.kv.max_ctx), bool)
    for s_ in (s0, s1):
        written[s_, lens0[s_]:lens0[s_] + rem[s_]] = True
    for l0, lk, lx, g in zip(jax.tree_util.tree_leaves(before),
                             jax.tree_util.tree_leaves(ker.kv.caches),
                             jax.tree_util.tree_leaves(xla.kv.caches),
                             jax.tree_util.tree_leaves(ker.kv._grouped)):
        lk, lx = np.asarray(lk), np.asarray(lx)
        np.testing.assert_allclose(lk, lx, rtol=1e-5, atol=1e-5)
        lay = range(lk.shape[0]) if g else [None]
        for i in lay:
            a, b = (l0[i], lk[i]) if g else (l0, lk)
            np.testing.assert_array_equal(b[~written], a[~written])


def test_lane_kernel_warmup_compiles_per_chunk(monkeypatch, qwen_k128):
    """On the lane kernel's path the fused programs are keyed by scan
    length alone: the default warm-up compiles len(DECODE_CHUNKS), and a
    requested ctx grid collapses onto the chunks; the XLA path keeps its
    (steps, ctx) grid."""
    cfg, params = qwen_k128
    xla = ReplicaEngine(cfg, params, n_slots=2, max_ctx=128)
    xla.warmup_decode(chunks=(1, 4), ctx_limits=(64, 128))
    assert set(xla._fused) == {(1, 64), (1, 128), (4, 64), (4, 128)}

    monkeypatch.setattr(attention, "_kernel_platform", lambda: True)
    ker = ReplicaEngine(cfg, params, n_slots=2, max_ctx=128)
    ker.warmup_decode(chunks=(1, 4), ctx_limits=(64, 128))
    assert set(ker._fused) == {(1, None), (4, None)}
    ker.warmup_decode()
    assert set(ker._fused) == {(c, None) for c in DECODE_CHUNKS}


def test_lane_kernel_counts_length_bounded_kv_read(monkeypatch, qwen_k128):
    """On the lane kernel's path `kv_read` counts the positions the kernel
    fetches: each live lane-step's length rounded up to the block (16
    positions here), and `ctx` names no static bound."""
    cfg, params = qwen_k128
    monkeypatch.setattr(lane_kernel, "BLOCK_BYTES",
                        16 * cfg.n_kv_heads * cfg.head_dim * 4)
    eng, (s0, s1), nt, em = _kernel_engine(monkeypatch, cfg, params)
    assert eng._lane_block == 16
    assert (eng.kv.lengths[s0], eng.kv.lengths[s1]) == (37, 11)
    tr = eng.tracer = Tracer(enabled=True)
    rem = np.zeros(eng.kv.n_slots, np.int32)
    rem[s0], rem[s1] = 3, 7
    eng.decode_steps(nt, em, rem)
    chunk = next(r for r in tr.records if r.name == "engine.decode_chunk")
    assert chunk.attrs["ctx"] is None
    # s0 at 37-39: 3 blocks each; s1 at 11-16: one block, at 17: two
    assert chunk.attrs["kv_read"] == 3 * 48 + 6 * 16 + 32


# --------------------------------------------------------------------------- #
# length-trimmed flash-decode grid vs the jnp oracle
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("lens", [[5, 100, 37], [512, 1, 129], [64, 64, 64],
                                  [512, 512, 512]])
def test_trimmed_flash_decode_ragged(key, lens):
    B, S, H, Hkv, D = 3, 512, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    lens_a = jnp.asarray(lens, jnp.int32)
    want = ref.decode_attention_ref(q, k, v, lens_a)
    got_full = flash_decode_attention(q, k, v, lens_a, block_k=128)
    got_trim = flash_decode_attention(q, k, v, lens_a, block_k=128,
                                      max_len=max(lens))
    assert float(jnp.max(jnp.abs(got_full - want))) < 2e-5
    assert float(jnp.max(jnp.abs(got_trim - want))) < 2e-5


def test_trimmed_flash_decode_len_below_one_block(key):
    """All lengths < block_k: the grid collapses to one KV block."""
    B, S, H, Hkv, D = 2, 1024, 4, 1, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    lens_a = jnp.asarray([7, 130], jnp.int32)
    want = ref.decode_attention_ref(q, k, v, lens_a)
    got = flash_decode_attention(q, k, v, lens_a, block_k=256, max_len=130)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_ops_decode_attention_max_len_dispatch(key):
    from repro.kernels import ops
    B, S, H, Hkv, D = 2, 256, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    lens = jnp.asarray([33, 200], jnp.int32)
    a = ops.decode_attention(q, k, v, lens, impl="pallas", max_len=200)
    b = ops.decode_attention(q, k, v, lens, impl="xla", max_len=200)
    c = ops.decode_attention(q, k, v, lens, impl="xla")
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5
    assert float(jnp.max(jnp.abs(b - c))) < 2e-5


def test_model_decode_ctx_limit_matches_untrimmed(qwen):
    """Trimming the cache read to a live-length bound must not change
    logits (padding past kv_lens is fully masked either way)."""
    cfg, model, params = qwen
    eng, (s0, s1), nt, em = _prefill_two(cfg, params)
    lens = jnp.asarray(eng.kv.lengths)
    lg_full, _ = model.decode_step(params, jnp.asarray(nt), eng.kv.caches,
                                   lens, kv_lens=lens)
    lg_trim, _ = model.decode_step(params, jnp.asarray(nt), eng.kv.caches,
                                   lens, kv_lens=lens, ctx_limit=64)
    assert float(jnp.max(jnp.abs(lg_full - lg_trim))) < 1e-4


# --------------------------------------------------------------------------- #
# end-to-end: fused chunked serving == reference single-step serving
# --------------------------------------------------------------------------- #
def test_server_fused_matches_reference_end_to_end(qwen):
    cfg, model, params = qwen
    tc = TraceConfig(first_input_median=50, first_input_sigma=0.3,
                     first_input_max=120, append_median=14, append_sigma=0.4,
                     append_max=32, output_median=6, output_sigma=0.5,
                     output_max=10, mean_turns=2.0, max_turns=3,
                     tool_mean_s=0.02)

    def run(mode):
        trace = generate_trace(4, 3.0, cfg=tc)
        reps = [ReplicaEngine(cfg, params, n_slots=8, max_ctx=512,
                              replica_id=0, role="prefill"),
                ReplicaEngine(cfg, params, n_slots=8, max_ctx=512,
                              replica_id=1)]
        srv = EngineServer(make_scheduler("conserve"), reps,
                           decode_mode=mode, record_tokens=True,
                           strict_accounting=True, tracing=True)
        recs = srv.serve(trace)
        srv.check_accounting()
        return srv, recs

    s_ref, r_ref = run("reference")
    s_fus, r_fus = run("fused")
    assert s_ref.sampled_tokens == s_fus.sampled_tokens, \
        _first_divergence(s_ref, s_fus)
    a = sorted((c.cid, t.turn_idx, t.n_output_tokens)
               for c in r_ref for t in c.turns)
    b = sorted((c.cid, t.turn_idx, t.n_output_tokens)
               for c in r_fus for t in c.turns)
    assert a == b


def _first_divergence(ref, fus) -> str:
    """The first (cid, turn) whose streams differ between two traced
    servers, and each one's decode chunks in dispatch order as (node,
    program, scan steps, ctx bucket, emitting lanes)."""
    keys = sorted(set(ref.sampled_tokens) | set(fus.sampled_tokens))
    bad = next((k for k in keys if ref.sampled_tokens.get(k)
                != fus.sampled_tokens.get(k)), None)

    def chunks(srv):
        return [(r.attrs["node"], r.attrs["program"], r.attrs["n_steps"],
                 r.attrs["ctx"], r.attrs["lanes"])
                for r in sorted(srv.tracer.records, key=lambda r: r.t0)
                if r.name == "engine.decode_chunk"]

    return (f"first differing (cid, turn) {bad}: reference "
            f"{ref.sampled_tokens.get(bad)}, fused "
            f"{fus.sampled_tokens.get(bad)}; reference decode chunks "
            f"{chunks(ref)}; fused decode chunks {chunks(fus)}")


def _staggered_trace():
    """Four conversations arriving together whose outputs finish 2-20 steps
    apart — the worst case for min-collapsed chunking: slot 0 used to drag
    every chunk down to its tiny remaining.

    All arrivals are at t=0.0 exactly, so every conversation prefills
    (event push order) before the first decode chunk regardless of how
    warm the jit caches are — the queue composition, and hence each
    dispatch's ctx bucket, is identical on every run and in both decode
    modes. Context sizes are chosen to stay inside ONE ctx bucket
    (max length + max chunk < 64) so the trimmed-read width never flips
    with interleaving."""
    outs = (2, 5, 9, 20)
    convs = []
    for i, o in enumerate(outs):
        turns = [Turn(append_tokens=8 + 2 * i, output_tokens=o,
                      tool_time_s=0.0)]
        if i == 1:  # one multi-turn conv exercises chunk-boundary admission
            turns.append(Turn(append_tokens=10, output_tokens=6,
                              tool_time_s=0.0))
        convs.append(Conversation(cid=i, arrival_s=0.0, turns=turns))
    return convs


def test_server_staggered_finish_fused_matches_reference(qwen):
    """Short-output agentic trace with staggered finishes: ragged fused
    serving must produce byte-identical per-(cid, turn) token streams and
    turn records vs decode_mode="reference" — with the jitted prefill ON
    (the default) in both runs, and a third fully-eager run
    (prefill_mode="reference") matching the fused streams too."""
    cfg, model, params = qwen

    def run(mode, prefill_mode=None):
        rep = ReplicaEngine(cfg, params, n_slots=8, max_ctx=256,
                            replica_id=0, role="mixed")
        srv = EngineServer(make_scheduler("conserve"), [rep],
                           decode_mode=mode, record_tokens=True,
                           strict_accounting=True, prefill_mode=prefill_mode)
        recs = srv.serve(_staggered_trace())
        srv.check_accounting()
        return srv, {c.cid: c for c in recs}

    s_ref, r_ref = run("reference")
    s_fus, r_fus = run("fused")
    s_eag, _ = run("reference", prefill_mode="reference")
    assert s_eag.sampled_tokens == s_fus.sampled_tokens
    assert s_ref.sampled_tokens == s_fus.sampled_tokens
    assert sorted(r_ref) == sorted(r_fus)
    for cid in r_ref:
        a = [(t.turn_idx, t.n_output_tokens) for t in r_ref[cid].turns]
        b = [(t.turn_idx, t.n_output_tokens) for t in r_fus[cid].turns]
        assert a == b

    # mid-chunk finishes: on the fused run all four turn-0s decode in one
    # ragged chunk, so their last-token timestamps must interpolate in
    # output order instead of all landing on the chunk boundary
    fin = [r_fus[cid].turns[0].last_token_s for cid in range(4)]
    assert fin[0] < fin[1] < fin[2] < fin[3]


def test_server_rotation_matches_chunk_boundary_staggered(qwen):
    """Continuous rotation (adaptive chunk cuts + mid-tail refill) must
    serve the staggered trace with byte-identical per-(cid, turn) token
    streams and turn records vs the chunk-boundary-only baseline —
    rotation changes WHEN work runs, never WHAT it computes — while
    spending no more masked forwards and no more scan steps."""
    cfg, model, params = qwen

    def run(rotation):
        rep = ReplicaEngine(cfg, params, n_slots=8, max_ctx=256,
                            replica_id=0, role="mixed")
        srv = EngineServer(make_scheduler("conserve"), [rep],
                           record_tokens=True, strict_accounting=True,
                           rotation=rotation)
        recs = srv.serve(_staggered_trace())
        srv.check_accounting()
        return srv, {c.cid: c for c in recs}

    s_rot, r_rot = run(True)
    s_bnd, r_bnd = run(False)
    assert s_rot.sampled_tokens == s_bnd.sampled_tokens
    assert sorted(r_rot) == sorted(r_bnd)
    for cid in r_bnd:
        a = [(t.turn_idx, t.n_output_tokens) for t in r_bnd[cid].turns]
        b = [(t.turn_idx, t.n_output_tokens) for t in r_rot[cid].turns]
        assert a == b
    st_r, st_b = s_rot.states[0], s_bnd.states[0]
    assert st_r.decode_lane_steps_live == st_b.decode_lane_steps_live
    assert st_r.decode_scan_steps <= st_b.decode_scan_steps
    assert st_r.masked_forward_fraction <= st_b.masked_forward_fraction + 1e-9
