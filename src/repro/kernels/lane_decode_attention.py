"""Pallas TPU kernel: one decode layer's attention over the live lanes' KV,
read in place from the layer-stacked cache and bounded by each lane's own
length.

The engine's decode scan carries every layer's K and V in one leaf
(layers, lanes, max_ctx, Hkv, D). One call serves one layer: the layer
index, the lanes' lengths and their live mask ride in SMEM, the leaves stay
in HBM, and the kernel copies each live lane's blocks of `block_k`
positions, up to the block that holds its last position, into a double
buffer in VMEM; the next block (of the same lane or of the next live lane)
is in flight while the current one is scored. Lanes that are not live and
blocks past a lane's length are never fetched, so HBM traffic is each live
lane's own length rounded up to one block, and no trip of the kernel is
spent on them.

Inside a block the G query heads that share a KV head run as one
(G, D) x (D, block_k) matmul against that head's keys, with an f32 online
softmax; the probabilities are cast to the KV dtype before they contract
with the head's values. A block holds the heads interleaved per position
([block_k * Hkv, D] rows), so one head's keys are a strided row load:
plain for 32-bit KV, and for bf16 one 32-bit load yields a pair of
neighbouring heads, split by shifting their halves into place. The softmax
state of each lane starts from the new token's own entry (score q.k_new,
weight 1, value v_new), the same two-branch combine as the XLA path. Lanes
that are not live get zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_BYTES = 1 << 20  # one K (or V) block in VMEM


def lane_block_k(max_ctx: int, n_kv_heads: int, head_dim: int,
                 itemsize: int) -> int:
    """Positions per fetched block: the largest power of two whose K block
    stays within BLOCK_BYTES and divides `max_ctx`."""
    bk = 1
    while (bk * 2 * n_kv_heads * head_dim * itemsize <= BLOCK_BYTES
           and max_ctx % (bk * 2) == 0):
        bk *= 2
    return bk


def _head_rows(ref, n_kv_heads: int, block_k: int):
    """The keys (or values) of every KV head of one buffered block:
    `ref` is (block_k, Hkv, D); returns Hkv arrays of (block_k, D)."""
    rows = ref.reshape(block_k * n_kv_heads, ref.shape[-1])
    if jnp.dtype(ref.dtype).itemsize == 4:
        return [rows[pl.ds(n, block_k, stride=n_kv_heads), :]
                for n in range(n_kv_heads)]
    # bf16: heads 2c and 2c+1 of a position share one 32-bit row, the even
    # head in the low half
    words = rows.bitcast(jnp.uint32)
    out = []
    for c in range(n_kv_heads // 2):
        w = words[pl.ds(c, block_k, stride=n_kv_heads // 2), :]
        for half in (w << 16, w & jnp.uint32(0xFFFF0000)):
            out.append(pltpu.bitcast(half, jnp.float32).astype(ref.dtype))
    return out


def _lane_kernel(at_ref, lens_ref, live_ref, q_ref, kn_ref, vn_ref,
                 k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr,
                 lanes, *, block_k: int, scale: float):
    B, H, D = q_ref.shape
    Hkv = kn_ref.shape[1]
    G = H // Hkv
    layer = at_ref[0]
    o_ref[...] = jnp.zeros_like(o_ref)

    def gather(b, n):  # the live lanes, in order, at lanes[:n]
        lanes[n] = b
        return n + (live_ref[b] != 0).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, B, gather, jnp.int32(0))

    def n_blocks(b):
        return jnp.maximum(pl.cdiv(lens_ref[b], block_k), 1)

    def copies(b, i, slot):
        src = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, src], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, b, src], vbuf.at[slot],
                                      sem.at[1, slot]))

    @pl.when(n_live > 0)
    def _first():
        for c in copies(lanes[0], 0, 0):
            c.start()

    def lane(j, t):
        b = lanes[j]
        length = lens_ref[b]
        nb = n_blocks(b)
        for n in range(Hkv):  # the new token's branch opens the softmax
            q = q_ref[b, pl.ds(n * G, G), :].astype(jnp.float32)
            kn = kn_ref[b, pl.ds(n, 1), :].astype(jnp.float32)
            m_scr[n] = jnp.sum(q * kn, axis=1, keepdims=True) * scale
            l_scr[n] = jnp.ones((G, 1), jnp.float32)
            acc_scr[n] = jnp.broadcast_to(
                vn_ref[b, pl.ds(n, 1), :].astype(jnp.float32), (G, D))

        def block(i, t):
            slot = t % 2

            @pl.when(i + 1 < nb)
            def _next_block():
                for c in copies(b, i + 1, 1 - slot):
                    c.start()

            @pl.when((i + 1 == nb) & (j + 1 < n_live))
            def _next_lane():
                for c in copies(lanes[j + 1], 0, 1 - slot):
                    c.start()

            for c in copies(b, i, slot):
                c.wait()
            ks = _head_rows(kbuf.at[slot], Hkv, block_k)
            vs = _head_rows(vbuf.at[slot], Hkv, block_k)
            pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (G, block_k), 1)
            for n in range(Hkv):
                q = q_ref[b, pl.ds(n * G, G), :].astype(ks[n].dtype)
                s = jax.lax.dot_general(
                    q, ks[n], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(pos < length, s, NEG_INF)
                m_prev = m_scr[n]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_scr[n] = l_scr[n] * corr + p.sum(axis=1, keepdims=True)
                acc_scr[n] = acc_scr[n] * corr + jax.lax.dot(
                    p.astype(vs[n].dtype), vs[n],
                    preferred_element_type=jnp.float32)
                m_scr[n] = m_new
            return t + 1

        t = jax.lax.fori_loop(0, nb, block, t)
        for n in range(Hkv):
            o_ref[b, pl.ds(n * G, G), :] = acc_scr[n] / l_scr[n]
        return t

    jax.lax.fori_loop(0, n_live, lane, jnp.int32(0))


def lane_decode_attention(q, k_new, v_new, k, v, layer, lens, live, *,
                          block_k: int | None = None,
                          interpret: bool = True):
    """q: (B, H, D); k_new, v_new: (B, Hkv, D), the new token's entries;
    k, v: (NL, B, L, Hkv, D) layer-stacked leaves, read at `layer` (a traced
    scalar) and never copied; lens (B,): each lane's cached length, its
    positions [0, lens[b]) attended besides the new token; live (B,) bool.
    Returns (B, H, D) float32, zeros for lanes that are not live."""
    B, H, D = q.shape
    NL, _, L, Hkv, _ = k.shape
    G = H // Hkv
    if block_k is None:
        block_k = lane_block_k(L, Hkv, D, jnp.dtype(k.dtype).itemsize)
    assert L % block_k == 0, "the cache length must be a block multiple"
    if jnp.dtype(k.dtype).itemsize != 4:
        assert Hkv % 2 == 0, "16-bit KV needs KV heads in pairs"
    kernel = functools.partial(_lane_kernel, block_k=block_k,
                               scale=1.0 / math.sqrt(D))
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, lens, live
        grid=(1,),
        in_specs=[whole(B, H, D), whole(B, Hkv, D), whole(B, Hkv, D),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole(B, H, D),
        scratch_shapes=[
            pltpu.VMEM((2, block_k, Hkv, D), k.dtype),
            pltpu.VMEM((2, block_k, Hkv, D), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.SMEM((B,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), jnp.float32),
        name="lane_decode_attention",
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      lens.astype(jnp.int32), live.astype(jnp.int32),
      q, k_new, v_new, k, v)
