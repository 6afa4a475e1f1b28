"""Pallas TPU kernel: flash-decode GQA attention — the memory-bound tail
phase ConServe pins to decoders. One query token per sequence reads a long
KV cache; the kernel streams KV blocks HBM->VMEM with online-softmax
accumulation, so HBM KV bandwidth is the only roofline term (matching §3.2's
characterization).

Block layout: one grid step takes a (block_k, Hkv, D) tile of the
(B, S, Hkv, D) cache — ALL KV heads of block_k consecutive positions, one
contiguous HBM region. The TPU lowering requires a block's last two
dimensions to be (8, 128)-aligned or to span the array, so a per-head
(block_k, 1, D) block is refused; spanning Hkv satisfies the rule for any
head count. Inside the step a static loop over KV heads runs the G query
heads sharing that KV head as one (G, D) x (D, block_k) MXU matmul.

Length trimming: the grid is a scalar-prefetch grid
(`pltpu.PrefetchScalarGridSpec`) whose KV-block index map clamps the block
index to each sequence's last *live* block — once `k_start >= valid_len`
the map revisits the previous block, so Pallas's revisit-elision never
issues the HBM->VMEM DMA for dead cache tail blocks. Callers that know a
static upper bound on the live lengths pass `max_len` and the grid itself
shrinks to `ceil(max_len / block_k)` KV steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_k: int, n_kv_heads: int, scale: float):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    b = pl.program_id(0)
    valid_len = len_ref[b]
    k_start = ki * block_k

    @pl.when(k_start < valid_len)
    def _compute():
        for n in range(n_kv_heads):
            q = q_ref[0, n].astype(jnp.float32)        # (G, D)
            k = k_ref[0, :, n, :].astype(jnp.float32)  # (block_k, D)
            v = v_ref[0, :, n, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < valid_len, s, NEG_INF)
            m_prev = m_scr[n]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[n] = l_scr[n] * corr + p.sum(axis=1, keepdims=True)
            acc_scr[n] = acc_scr[n] * corr + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[n] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


def flash_decode_attention(q, k, v, lengths=None, *, block_k: int = 256,
                           max_len: int | None = None,
                           interpret: bool = True):
    """q: (B, H, D); k,v: (B, S, Hkv, D); lengths: (B,) valid KV lengths
    (None = all S valid). `max_len` is an optional STATIC upper bound on
    `lengths`; when given, the KV grid only spans ceil(max_len / block_k)
    blocks instead of S / block_k. Returns (B, H, D)."""
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0, "pad cache length to a block multiple"
    nk = S // block_k
    if max_len is not None:
        if lengths is None and max_len < S:
            raise ValueError(
                "max_len < S with lengths=None would silently truncate "
                "attention to the first max_len positions; pass lengths")
        nk = max(1, min(nk, -(-int(max_len) // block_k)))
    scale = 1.0 / math.sqrt(D)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = lengths.astype(jnp.int32)
    qg = q.reshape(B, Hkv, G, D)

    def kv_block(b, ki, lens):
        # clamp to the last live block: dead tail blocks revisit it, which
        # Pallas elides — no HBM fetch past each sequence's valid length.
        last_live = jnp.maximum(pl.cdiv(lens[b], block_k) - 1, 0)
        return (b, jnp.minimum(ki, last_live), 0, 0)

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               n_kv_heads=Hkv, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths ride in SMEM ahead of the grid
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, ki, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, Hkv, D), kv_block),
            pl.BlockSpec((1, block_k, Hkv, D), kv_block),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D),
                               lambda b, ki, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, H, D)
