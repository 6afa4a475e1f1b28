"""Attention: blocked online-softmax prefill (global / sliding-window), GQA
decode against a (possibly length-sharded) KV cache, and MLA (DeepSeek-style
latent attention) with the absorbed-matrix decode path.

Conventions
-----------
* Prefill/train attention expands GQA KV heads to full `n_heads` before the
  einsums (KV projections are small and kept replicated under TP; Q heads are
  the TP-sharded dimension).
* Decode attention keeps Q replicated and shards the *KV length* dimension —
  context-parallel flash-decode, matching the memory-bound tail of the paper.
* `gqa_decode` returns the new token's KV entries and leaves the append to
  the engine/cache-manager (see DESIGN.md §5). The engine's own decode step,
  `gqa_decode_lanes`, writes that row into the layer-stacked cache itself
  and reads only the live lanes' KV from it, in place.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA, ModelConfig
from .layers import apply_rope, sds

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Param skeletons
# --------------------------------------------------------------------------- #
def attn_skeleton(cfg: ModelConfig, kind: str, cross: bool = False) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim
    if kind == ATTN_MLA:
        qd = cfg.qk_nope_dim + cfg.qk_rope_dim
        sk = {
            "w_dkv": sds((d, cfg.kv_lora_rank + cfg.qk_rope_dim), cfg.dtype),
            "w_uk": sds((cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim), cfg.dtype),
            "w_uv": sds((cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim), cfg.dtype),
            "wo": sds((cfg.n_heads * cfg.v_head_dim, d), cfg.dtype),
        }
        if cfg.q_lora_rank:
            sk["w_dq"] = sds((d, cfg.q_lora_rank), cfg.dtype)
            sk["w_uq"] = sds((cfg.q_lora_rank, cfg.n_heads * qd), cfg.dtype)
        else:
            sk["wq"] = sds((d, cfg.n_heads * qd), cfg.dtype)
        return sk
    sk = {
        "wq": sds((d, cfg.n_heads * hd), cfg.dtype),
        "wk": sds((d, cfg.n_kv_heads * hd), cfg.dtype),
        "wv": sds((d, cfg.n_kv_heads * hd), cfg.dtype),
        "wo": sds((cfg.n_heads * hd, d), cfg.dtype),
    }
    if cfg.qk_norm and not cross:
        sk["q_scale"] = sds((hd,), cfg.dtype)
        sk["k_scale"] = sds((hd,), cfg.dtype)
    return sk


def rope_single(x, positions, theta: float):
    """RoPE for a single decode step with PER-SEQUENCE positions.
    x: (B, 1, H, D) or (B, 1, D); positions: (B,) or scalar int32."""
    from .layers import rope_freqs
    pos = jnp.asarray(positions, jnp.float32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (x.shape[0],))
    dim = x.shape[-1]
    inv = rope_freqs(dim, theta)
    ang = pos[:, None] * inv  # (B, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _qk_norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _repeat_kv(k, n_heads):
    """(B, T, Hkv, D) -> (B, T, H, D)."""
    reps = n_heads // k.shape[2]
    if reps == 1:
        return k
    return jnp.repeat(k, reps, axis=2)


# --------------------------------------------------------------------------- #
# Blocked online-softmax attention (the jnp flash oracle)
# --------------------------------------------------------------------------- #
def online_attention(
    q, k, v, q_pos, kv_pos, *, causal: bool = True, window: int = 0,
    q_chunk: int = 256, kv_chunk: int = 512, kv_lens=None, kv_valid=None,
):
    """q: (B,Sq,H,D); k,v: (B,Skv,H,D); q_pos: (Sq,), kv_pos: (Skv,) int32.

    Scans over Q chunks, inner-scans over KV chunks with online softmax —
    structurally the flash algorithm, bounding temporaries to
    (B, H, q_chunk, kv_chunk). `kv_lens` (B,) optionally masks per-batch
    ragged valid lengths; `kv_valid` (B, Skv) bool is the general per-entry
    validity mask (engine slot buffers)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)

    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pk), constant_values=jnp.iinfo(jnp.int32).max)
        if kv_valid is not None:
            kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pk)))

    nq, nk = (Sq + pq) // q_chunk, (Skv + pk) // kv_chunk
    qc = q.reshape(B, nq, q_chunk, H, D)
    kc = k.reshape(B, nk, kv_chunk, H, D)
    vc = v.reshape(B, nk, kv_chunk, H, D)
    qpc = q_pos.reshape(nq, q_chunk)
    kpc = kv_pos.reshape(nk, kv_chunk)
    kvc = (kv_valid.reshape(B, nk, kv_chunk).transpose(1, 0, 2)
           if kv_valid is not None else None)

    def q_step(_, qi):
        q_blk, qp = qi  # (B,Cq,H,D), (Cq,)

        def kv_step(carry, ki):
            m, l, acc = carry
            if kvc is not None:
                k_blk, v_blk, kp, kval = ki
            else:
                k_blk, v_blk, kp = ki
                kval = None
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            ok = (kp[None, :] >= 0) & (qp[:, None] >= 0)
            if causal:
                ok &= kp[None, :] <= qp[:, None]
            if window:
                ok &= kp[None, :] > qp[:, None] - window
            mask = ok[None, None]
            if kv_lens is not None:
                mask = mask & (kp[None, None, None, :]
                               < kv_lens[:, None, None, None])
            if kval is not None:
                mask = mask & kval[:, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, D), jnp.float32)
        xs = (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), kpc)
        if kvc is not None:
            xs = (*xs, kvc)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), xs)
        out = acc / jnp.maximum(l[..., None], 1e-20)
        return None, out.transpose(0, 2, 1, 3)  # (B,Cq,H,D)

    _, outs = jax.lax.scan(q_step, None,
                           (qc.transpose(1, 0, 2, 3, 4), qpc))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq + pq, H, D)
    return out[:, :Sq].astype(q.dtype)


# --------------------------------------------------------------------------- #
# Custom-VJP flash attention (training memory; §Perf iteration 1)
#
# jax.lax.scan's backward saves every step's online-softmax carriers
# (m, l, acc) — O(S·D) per KV chunk per layer, the dominant train-time
# temporary. The custom VJP saves only (out, lse) and RECOMPUTES attention
# probabilities chunk-by-chunk in the backward pass — the flash-attention
# backward, in pure jnp.
# --------------------------------------------------------------------------- #
def _flash_fwd_impl(q, k, v, q_start, kv_start, causal, window, kv_chunk):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    kv_chunk = min(kv_chunk, Skv)
    pk = (-Skv) % kv_chunk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nk = (Skv + pk) // kv_chunk
    kc = k.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
    qpos = q_start + jnp.arange(Sq)

    def step(carry, ji):
        m, l, acc = carry
        k_blk, v_blk, j = ji
        kpos = kv_start + j * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        ok = kpos[None, :] < Skv + kv_start
        ok &= (kpos[None, :] <= qpos[:, None]) if causal else ok
        if window:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(ok[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nk)))
    out = (acc / jnp.maximum(l[..., None], 1e-20)).transpose(0, 2, 1, 3)
    lse = m + jnp.log(jnp.maximum(l, 1e-20))  # (B, H, Sq)
    return out.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, q_start, kv_start, causal, window, kv_chunk):
    """q: (B,Sq,H,D); k,v: (B,Skv,H,D) (heads pre-expanded). Causal /
    sliding-window attention with O(1)-in-S saved residuals."""
    return _flash_fwd_impl(q, k, v, q_start, kv_start, causal, window,
                           kv_chunk)[0]


def _flash_fwd(q, k, v, q_start, kv_start, causal, window, kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, q_start, kv_start, causal, window,
                               kv_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(q_start, kv_start, causal, window, kv_chunk, res, dout):
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    kv_chunk = min(kv_chunk, Skv)
    pk = (-Skv) % kv_chunk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nk = (Skv + pk) // kv_chunk
    kc = k.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
    qpos = q_start + jnp.arange(Sq)
    do = dout.astype(jnp.float32)
    # Delta_i = rowsum(dout * out)
    Dl = jnp.einsum("bqhd,bqhd->bhq", do, out.astype(jnp.float32))

    def step(dq, ji):
        k_blk, v_blk, j = ji
        kpos = kv_start + j * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        ok = kpos[None, :] < Skv + kv_start
        ok &= (kpos[None, :] <= qpos[:, None]) if causal else ok
        if window:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        p = jnp.where(ok[None, None],
                      jnp.exp(s - lse[..., None]), 0.0)  # recomputed probs
        dv = jnp.einsum("bhqk,bqhd->bkhd", p, do)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do, v_blk.astype(jnp.float32))
        ds = p * (dp - Dl[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk.astype(jnp.float32))
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, (kc, vc, jnp.arange(nk)))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Skv + pk, H, D)[:, :Skv]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Skv + pk, H, D)[:, :Skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------- #
# KV-cache quantization (decode tail; §Perf iteration 3)
# --------------------------------------------------------------------------- #
def quantize_kv(x, cfg: ModelConfig):
    if not cfg.kv_cache_dtype or cfg.kv_cache_dtype == cfg.dtype:
        return x
    s = cfg.kv_quant_scale
    return jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(
        jnp.dtype(cfg.kv_cache_dtype))


def dequantize_kv(x, cfg: ModelConfig):
    if not cfg.kv_cache_dtype or x.dtype == cfg.jnp_dtype:
        return x
    return (x.astype(jnp.float32) * cfg.kv_quant_scale).astype(cfg.jnp_dtype)


def local_attention(q, k, v, q_start: int, window: int, *,
                    q_chunk: int = 256):
    """Sliding-window causal attention, linear in sequence length.

    q, k, v: (B, S, H, D) aligned (kv covers the same positions as q plus any
    cached prefix to the left already included in k/v). Each Q chunk slices
    exactly `window + q_chunk` keys via dynamic_slice — O(S·W) total."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    prefix = Skv - Sq  # cached tokens to the left of q
    scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    pq = (-Sq) % q_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    nq = (Sq + pq) // q_chunk
    span = window + q_chunk  # keys visible to one q chunk
    # left-pad kv so every slice is in-bounds
    k_pad = jnp.pad(k, ((0, 0), (span, 0), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (span, 0), (0, 0), (0, 0)))

    def q_step(_, i):
        q_blk = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        # keys ending exactly at this chunk's end (global index prefix+i*Cq+Cq)
        start = prefix + i * q_chunk + q_chunk + span - span  # = prefix+i*Cq+Cq
        k_blk = jax.lax.dynamic_slice_in_dim(k_pad, start, span, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_pad, start, span, axis=1)
        qp = q_start + i * q_chunk + jnp.arange(q_chunk)
        kp = q_start + i * q_chunk + q_chunk - span + jnp.arange(span)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        ok = (kp[None, :] <= qp[:, None]) & (kp[None, :] > qp[:, None] - window)
        ok &= kp[None, :] >= 0
        s = jnp.where(ok[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        return None, out

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq + pq, H, D)
    return out[:, :Sq].astype(q.dtype)


# --------------------------------------------------------------------------- #
# Decode attention (context-parallel flash-decode)
# --------------------------------------------------------------------------- #
def _partial_softmax(s, mask):
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    return m, p.sum(-1), p


def _combine(m_c, l_c, o_c, m_n, l_n, o_n):
    """Merge the cache branch and the new token's branch of one-token
    attention (flash combine of two partial softmaxes)."""
    m = jnp.maximum(m_c, m_n)
    c_c, c_n = jnp.exp(m_c - m), jnp.exp(m_n - m)
    l = l_c * c_c + l_n * c_n
    return (o_c * c_c[..., None] + o_n * c_n[..., None]) / jnp.maximum(
        l[..., None], 1e-20)


def decode_attention(q1, k_cache, v_cache, k_new, v_new, *,
                     kv_lens=None, window: int = 0, pos=None):
    """One-token GQA attention against cache + the freshly produced token.

    q1: (B, 1, H, D); caches: (B, L, Hkv, D); new: (B, 1, Hkv, D).
    Uses a two-branch flash combine so the (possibly length-sharded) cache is
    read-only and never concatenated with the new token."""
    B, _, H, D = q1.shape
    L = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q1.reshape(B, Hkv, G, D)

    s_c = jnp.einsum("bngd,blnd->bngl", qg, k_cache,
                     preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(L)
    mask_c = jnp.ones((B, 1, 1, L), bool)
    if kv_lens is not None:
        mask_c &= idx[None, None, None, :] < kv_lens[:, None, None, None]
    if window and pos is not None:
        p_ = jnp.asarray(pos)
        p_ = p_.reshape(-1, 1, 1, 1) if p_.ndim else p_
        mask_c &= idx[None, None, None, :] > (p_ - window)
    m_c, l_c, p_c = _partial_softmax(s_c, mask_c)
    o_c = jnp.einsum("bngl,blnd->bngd", p_c, v_cache.astype(jnp.float32))

    s_n = jnp.einsum("bngd,blnd->bngl", qg, k_new,
                     preferred_element_type=jnp.float32) * scale
    m_n, l_n, p_n = _partial_softmax(s_n, jnp.ones_like(s_n, bool))
    o_n = jnp.einsum("bngl,blnd->bngd", p_n, v_new.astype(jnp.float32))

    out = _combine(m_c, l_c, o_c, m_n, l_n, o_n)
    return out.reshape(B, 1, H, D).astype(q1.dtype)


def write_rows(cache: Dict, rows: Dict, at: Tuple, lens, live) -> Dict:
    """Select-guarded in-place write of one decode step's new entries: leaf
    `cache[n]`, indexed down to (B, L, ...) by the leading indices `at`,
    takes `rows[n][b, 0]` at (*at, b, lens[b]) for every live lane b. A lane
    that is not live writes back its own bytes, so its row stays
    byte-identical, as in the engine's `fold_decode_step`; an index past
    the leaf's length (a windowed cache shorter than the context) is
    dropped, as there."""
    b = jnp.arange(live.shape[0])

    def put(leaf, row):
        idx = (*at, b, lens)
        row = row[:, 0].astype(leaf.dtype)
        keep = live.reshape((-1,) + (1,) * (row.ndim - 1))
        return leaf.at[idx].set(jnp.where(keep, row, leaf[idx]))

    return {**cache, **{n: put(cache[n], r) for n, r in rows.items()}}


def lane_decode_attention(cfg: ModelConfig, q1, k_new, v_new, k_leaf, v_leaf,
                          at: Tuple, lens, live, *,
                          ctx_limit: Optional[int] = None, window: int = 0):
    """One-token GQA attention that reads only the live lanes' KV, in place.

    q1: (B, 1, H, D); new: (B, 1, Hkv, D); leaves: (*lead, B, L, Hkv, D),
    indexed down to one layer by `at`. A loop whose trip count is the number
    of live lanes reads each live lane's first `ctx_limit` positions
    straight from the leaves as [C*Hkv, D] rows, a view of the stored
    layout: the scores are those rows against every query head, of which
    each head keeps its own KV head's; the probabilities, spread back over
    the rows with zeros elsewhere (cast to the KV dtype, the precision of
    the chip's default matmul), contract with the value rows. No slab
    spanning several lanes is materialized. The same two-branch combine as
    `decode_attention`; lanes that are not live get zeros."""
    B, _, H, D = q1.shape
    Hkv = k_leaf.shape[-2]
    G = H // Hkv
    L = k_leaf.shape[len(at) + 1]
    C = L if ctx_limit is None else min(ctx_limit, L)
    scale = 1.0 / math.sqrt(D)
    own = (jnp.arange(H) // G)[None, :] == jnp.arange(Hkv)[:, None]  # Hkv, H
    # the new token's branch for every lane: one score per head
    s_n = jnp.einsum("bhd,bhd->bh", q1[:, 0], _repeat_kv(k_new, H)[:, 0],
                     preferred_element_type=jnp.float32) * scale
    o_n = _repeat_kv(v_new, H)[:, 0].astype(jnp.float32)
    slots = jnp.nonzero(live, size=B, fill_value=0)[0]
    idx = jnp.arange(C)

    def lane(j, out):
        b = slots[j]
        start = (*at, b, 0, 0, 0)
        size = (1,) * len(at) + (1, C, Hkv, D)
        kr = dequantize_kv(jax.lax.dynamic_slice(k_leaf, start, size),
                           cfg).reshape(C * Hkv, D)
        vr = dequantize_kv(jax.lax.dynamic_slice(v_leaf, start, size),
                           cfg).reshape(C * Hkv, D)
        st = jnp.dot(kr, q1[b, 0].T, preferred_element_type=jnp.float32)
        s = jnp.where(own[None], st.reshape(C, Hkv, H), 0).sum(1) * scale
        mask = idx < lens[b]
        if window:
            mask &= idx > lens[b] - window
        s = jnp.where(mask[:, None], s, NEG_INF)  # (C, H)
        m_c = s.max(0)
        p = jnp.exp(s - m_c[None])
        l_c = p.sum(0)
        pr = jnp.where(own[None], p[:, None, :], 0).astype(vr.dtype)
        o_c = jax.lax.dot_general(pr.reshape(C * Hkv, H), vr,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o = _combine(m_c, l_c, o_c, s_n[b], jnp.ones_like(s_n[b]), o_n[b])
        return jax.lax.dynamic_update_slice(out, o[None], (b, 0, 0))

    out = jax.lax.fori_loop(0, live.sum(), lane,
                            jnp.zeros((B, H, D), jnp.float32))
    return out[:, None].astype(q1.dtype)


# --------------------------------------------------------------------------- #
# Full attention blocks (projection + rope + attention + output)
# --------------------------------------------------------------------------- #
def _proj_qkv(params, cfg: ModelConfig, x):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ params["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if "q_scale" in params:
        q = _qk_norm(q, params["q_scale"])
        k = _qk_norm(k, params["k_scale"])
    return q, k, v


def gqa_prefill(params, cfg: ModelConfig, kind: str, x, start_pos: int,
                prefix_kv: Optional[Dict] = None, kv_lens=None,
                prefix_start: Optional[int] = None,
                attention_impl: str = "xla"):
    """Prefill / append-prefill. Returns (out, {"k","v"} new-token cache).

    prefix_kv layouts:
      * default (prefix_start=None): the prefix buffer ends exactly at
        start_pos (contiguous history, dry-run / exact append).
      * engine slots (prefix_start=0): the prefix buffer starts at position
        0 and may be right-padded beyond the live length; pass kv_lens to
        mask the padding.

    `attention_impl="pallas"` (static) routes FRESH global-attention
    prefill (no prefix, no kv_lens masking, no window) through the
    flash-prefill kernel — native on TPU, interpret-mode elsewhere. The
    kernel computes plain causal attention over the padded bucket, which
    is exactly what the engine's turn-1 prefill needs (padded positions
    attend only rightward of the live tokens; their outputs and KV are
    discarded/masked by the caller). Append-prefill prefix reads, ragged
    kv_lens masks and sliding windows fall back to the jnp paths below.
    """
    B, S, _ = x.shape
    with jax.named_scope("qkv"):
        q, k, v = _proj_qkv(params, cfg, x)
        theta = cfg.rope_theta if kind == ATTN_GLOBAL else getattr(
            cfg, "rope_theta_local", cfg.rope_theta)
        pos = start_pos + jnp.arange(S)
        q = apply_rope(q, pos, theta)
        k = apply_rope(k, pos, theta)
    new_cache = {"k": k, "v": v}
    with jax.named_scope("core"):
        window = cfg.window if kind == ATTN_LOCAL else 0
        if prefix_kv is not None:
            P = prefix_kv["k"].shape[1]
            pstart = (start_pos - P) if prefix_start is None else prefix_start
            kv_pos = jnp.concatenate([pstart + jnp.arange(P), pos])
            k_all = jnp.concatenate(
                [_repeat_kv(prefix_kv["k"], cfg.n_heads),
                 _repeat_kv(k, cfg.n_heads)], axis=1)
            v_all = jnp.concatenate(
                [_repeat_kv(prefix_kv["v"], cfg.n_heads),
                 _repeat_kv(v, cfg.n_heads)], axis=1)
            kv_valid = None
            if kv_lens is not None:
                # padding lives only in the prefix region; new tokens are
                # valid
                kv_valid = jnp.concatenate(
                    [jnp.arange(P)[None, :] < kv_lens[:, None],
                     jnp.ones((x.shape[0], S), bool)], axis=1)
            out = online_attention(q, k_all, v_all, pos, kv_pos, causal=True,
                                   window=window, kv_valid=kv_valid)
        else:
            kf = _repeat_kv(k, cfg.n_heads)
            vf = _repeat_kv(v, cfg.n_heads)
            use_pallas = (attention_impl == "pallas" and kv_lens is None
                          and window == 0
                          and (S <= 128 or S % 128 == 0))
            if use_pallas:
                from repro.kernels import ops
                out = ops.prefill_attention(q, kf, vf, window=0,
                                            impl="pallas")
            elif (cfg.flash_vjp and kv_lens is None
                  and not cfg.attn_block_full):
                out = flash_attention(q, kf, vf, start_pos, start_pos, True,
                                      window, 512)
            elif (kind == ATTN_LOCAL and cfg.window
                  and not cfg.attn_block_full):
                out = local_attention(q, kf, vf, start_pos, cfg.window)
            else:
                kv_pos = start_pos + jnp.arange(S)
                ch = (1 << 30) if cfg.attn_block_full else 256
                out = online_attention(q, kf, vf, pos, kv_pos, causal=True,
                                       window=window, kv_lens=kv_lens,
                                       q_chunk=ch, kv_chunk=ch)
    with jax.named_scope("out"):
        out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
        return out @ params["wo"], new_cache


def _trim_ctx(leaf, ctx_limit: Optional[int]):
    """Static slice of a growing cache leaf's length axis (axis 1) to the
    caller-provided live-length upper bound — the decode tail then reads
    only the live KV prefix instead of the whole max_ctx buffer."""
    if ctx_limit is None or leaf.shape[1] <= ctx_limit:
        return leaf
    return leaf[:, :ctx_limit]


def _kernel_platform() -> bool:
    """Whether the Pallas kernels run natively here (the TPU)."""
    return jax.default_backend() == "tpu"


def lane_kernel_serves(cfg: ModelConfig, kind: str) -> bool:
    """Whether a layer of `kind` reads the live lanes' KV through the
    length-bounded Pallas kernel (`kernels/lane_decode_attention.py`)
    rather than the XLA lane loop: on the TPU, for global GQA attention (no
    window) over KV stored unquantized in bf16 or f32, with head_dim a lane
    multiple and the KV heads filling whole 32-bit rows."""
    if kind != ATTN_GLOBAL or not _kernel_platform():
        return False
    if cfg.kv_cache_dtype and cfg.kv_cache_dtype != cfg.dtype:
        return False
    dt = jnp.dtype(cfg.jnp_dtype)
    return (dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and cfg.head_dim % 128 == 0
            and cfg.n_kv_heads % (4 // dt.itemsize) == 0)


def lanes_length_bounded(cfg: ModelConfig) -> bool:
    """Whether every layer that reads a growing cache in the engine's
    in-place decode step bounds its read by each lane's length (the Pallas
    kernel), so the step needs no static context bound."""
    reads = {k for k in cfg.block_pattern
             if k in (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA)}
    return bool(reads) and all(lane_kernel_serves(cfg, k) for k in reads)


def gqa_decode_lanes(params, cfg: ModelConfig, kind: str, x1, cache: Dict,
                     at: Tuple, lens, live, ctx_limit: Optional[int] = None):
    """The engine's in-place GQA decode step. cache: {"k","v"} leaves
    (*lead, B, L, Hkv, hd) that `at` indexes down to this layer; `lens` is
    each lane's length and position. The new token's K/V row is written in
    place first (`write_rows`, select-guarded by `live`), then attention
    reads only the live lanes' KV: through the length-bounded Pallas kernel
    where `lane_kernel_serves`, each lane up to its own length, else through
    the XLA lane loop up to `ctx_limit` (`lane_decode_attention`). Returns
    (out, cache with the rows written)."""
    with jax.named_scope("qkv"):
        q, k, v = _proj_qkv(params, cfg, x1)
        theta = cfg.rope_theta if kind == ATTN_GLOBAL else getattr(
            cfg, "rope_theta_local", cfg.rope_theta)
        q = rope_single(q, lens, theta)
        k = rope_single(k, lens, theta)
    with jax.named_scope("core"):
        with jax.named_scope("kv_fold"):
            cache = write_rows(cache, {"k": quantize_kv(k, cfg),
                                       "v": quantize_kv(v, cfg)},
                               at, lens, live)
        if lane_kernel_serves(cfg, kind):
            from repro.kernels import ops
            k_leaf, v_leaf = cache["k"], cache["v"]
            if not at:  # an unstacked layer: a stack of one
                k_leaf, v_leaf = k_leaf[None], v_leaf[None]
            out = ops.lane_decode_attention(
                q[:, 0], k[:, 0], v[:, 0], k_leaf, v_leaf,
                at[0] if at else 0, lens, live)
            out = out[:, None].astype(q.dtype)
        else:
            out = lane_decode_attention(
                cfg, q, k, v, cache["k"], cache["v"], at, lens, live,
                ctx_limit=ctx_limit,
                window=cfg.window if kind == ATTN_LOCAL else 0)
    with jax.named_scope("out"):
        out = out.reshape(x1.shape[0], 1, cfg.n_heads * cfg.head_dim)
        return out @ params["wo"], cache


def gqa_decode(params, cfg: ModelConfig, kind: str, x1, position,
               cache: Dict, kv_lens=None, ctx_limit: Optional[int] = None,
               attention_impl: str = "xla"):
    """x1: (B,1,D); cache: {"k","v"} (B,L,Hkv,hd); position scalar or (B,).
    `ctx_limit` (static) is an upper bound on kv_lens: the cache read is
    trimmed to it. `attention_impl="pallas"` (static) routes global-attention
    decode through the flash-decode kernel (scalar-prefetch trimmed grid —
    native on TPU, interpret-mode elsewhere); cases the kernel does not
    cover (no kv_lens, sliding window, non-block-multiple trimmed length)
    fall back to the jnp two-branch combine. Returns (out, new_kv)."""
    with jax.named_scope("qkv"):
        q, k, v = _proj_qkv(params, cfg, x1)
        theta = cfg.rope_theta if kind == ATTN_GLOBAL else getattr(
            cfg, "rope_theta_local", cfg.rope_theta)
        q = rope_single(q, position, theta)
        k = rope_single(k, position, theta)
    with jax.named_scope("core"):
        window = cfg.window if kind == ATTN_LOCAL else 0
        k_c = dequantize_kv(_trim_ctx(cache["k"], ctx_limit), cfg)
        v_c = dequantize_kv(_trim_ctx(cache["v"], ctx_limit), cfg)
        S = k_c.shape[1]
        use_pallas = (attention_impl == "pallas" and kv_lens is not None
                      and window == 0 and (S <= 256 or S % 256 == 0))
        if use_pallas:
            from repro.kernels import ops
            B = q.shape[0]
            lens = jnp.asarray(kv_lens, jnp.int32)
            # The kernel reads one contiguous buffer, so the fresh token's
            # K/V is placed at each sequence's live length (engine callers
            # guarantee kv_lens < the trimmed buffer length: the slot has
            # append room). This stages a scattered copy of the trimmed
            # read — the fetch-trimming happens inside the kernel grid,
            # which never spans past max(lens)+1 when the caller also
            # passes a tight ctx_limit.
            idx = jnp.arange(B)
            k_all = k_c.at[idx, lens].set(k[:, 0].astype(k_c.dtype))
            v_all = v_c.at[idx, lens].set(v[:, 0].astype(v_c.dtype))
            out = ops.decode_attention(q[:, 0], k_all, v_all, lens + 1,
                                       impl="pallas")[:, None]
        else:
            out = decode_attention(q, k_c, v_c, k, v, kv_lens=kv_lens,
                                   window=window, pos=jnp.asarray(position))
    with jax.named_scope("out"):
        out = out.reshape(x1.shape[0], 1, cfg.n_heads * cfg.head_dim)
        return out @ params["wo"], {"k": quantize_kv(k, cfg),
                                    "v": quantize_kv(v, cfg)}


# --------------------------------------------------------------------------- #
# MLA (multi-head latent attention)
# --------------------------------------------------------------------------- #
def _mla_q(params, cfg: ModelConfig, x):
    B, S, _ = x.shape
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        q = (x @ params["w_dq"]) @ params["w_uq"]
    else:
        q = x @ params["wq"]
    q = q.reshape(B, S, cfg.n_heads, qd)
    return q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]


def mla_prefill(params, cfg: ModelConfig, x, start_pos: int,
                prefix_kv: Optional[Dict] = None, kv_lens=None,
                prefix_start: Optional[int] = None):
    """Returns (out, {"ckv","krope"}): cache stores the compressed latent
    (kv_lora_rank) + shared rope key only — the MLA memory win."""
    B, S, _ = x.shape
    pos = start_pos + jnp.arange(S)
    q_nope, q_rope = _mla_q(params, cfg, x)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    dkv = x @ params["w_dkv"]  # (B,S,rank+rope)
    ckv, krope = dkv[..., : cfg.kv_lora_rank], dkv[..., cfg.kv_lora_rank:]
    krope = apply_rope(krope, pos, cfg.rope_theta)
    new_cache = {"ckv": ckv, "krope": krope}

    kv_valid = None
    if prefix_kv is not None:
        P = prefix_kv["ckv"].shape[1]
        ckv_all = jnp.concatenate([prefix_kv["ckv"], ckv], axis=1)
        krope_all = jnp.concatenate([prefix_kv["krope"], krope], axis=1)
        kv_start = (start_pos - P) if prefix_start is None else prefix_start
        if kv_lens is not None:
            kv_valid = jnp.concatenate(
                [jnp.arange(P)[None, :] < kv_lens[:, None],
                 jnp.ones((B, S), bool)], axis=1)
            kv_lens = None
    else:
        ckv_all, krope_all, kv_start = ckv, krope, start_pos

    # Expand latent to per-head K/V for the compute-bound prefill (standard
    # form; the absorbed form only pays off at decode).
    k_nope = jnp.einsum("blr,rhd->blhd", ckv_all, params["w_uk"])
    vv = jnp.einsum("blr,rhd->blhd", ckv_all, params["w_uv"])
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_all[:, :, None, :],
                                  (*k_nope.shape[:3], cfg.qk_rope_dim))], -1)
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    # pad V head_dim up to QK head_dim for the shared einsum, trim after
    kv_pos = kv_start + jnp.arange(ckv_all.shape[1])
    ch = (1 << 30) if cfg.attn_block_full else 256
    out = online_attention(q_full, k_full,
                           jnp.pad(vv, ((0, 0), (0, 0), (0, 0),
                                        (0, k_full.shape[-1] - vv.shape[-1]))),
                           pos, kv_pos, causal=True, kv_lens=kv_lens,
                           kv_valid=kv_valid, q_chunk=ch, kv_chunk=ch)
    out = out[..., : cfg.v_head_dim].reshape(B, S, cfg.n_heads * cfg.v_head_dim)
    return out @ params["wo"], new_cache


def mla_decode(params, cfg: ModelConfig, x1, position, cache: Dict,
               kv_lens=None, ctx_limit: Optional[int] = None):
    """Absorbed-matrix MLA decode: score through the latent space directly;
    attention reads c_kv (rank) + k_rope (rope_dim) only."""
    B = x1.shape[0]
    q_nope, q_rope = _mla_q(params, cfg, x1)
    q_rope = rope_single(q_rope, position, cfg.rope_theta)
    # absorb W_uk into the query: (B,1,H,nope) @ (rank,H,nope) -> (B,1,H,rank)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, params["w_uk"])

    dkv = x1 @ params["w_dkv"]
    ckv_n, krope_n = dkv[..., : cfg.kv_lora_rank], dkv[..., cfg.kv_lora_rank:]
    krope_n = rope_single(krope_n, position, cfg.rope_theta)
    new_cache = {"ckv": quantize_kv(ckv_n, cfg),
                 "krope": quantize_kv(krope_n, cfg)}
    cache = {"ckv": dequantize_kv(_trim_ctx(cache["ckv"], ctx_limit), cfg),
             "krope": dequantize_kv(_trim_ctx(cache["krope"], ctx_limit),
                                    cfg)}

    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    L = cache["ckv"].shape[1]
    s_c = (jnp.einsum("bshr,blr->bshl", q_lat, cache["ckv"],
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bshd,bld->bshl", q_rope, cache["krope"],
                        preferred_element_type=jnp.float32)) * scale
    s_n = (jnp.einsum("bshr,blr->bshl", q_lat, ckv_n,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bshd,bld->bshl", q_rope, krope_n,
                        preferred_element_type=jnp.float32)) * scale
    mask_c = jnp.ones((B, 1, 1, L), bool)
    if kv_lens is not None:
        mask_c &= jnp.arange(L)[None, None, None, :] < kv_lens[:, None, None, None]
    m_c, l_c, p_c = _partial_softmax(s_c, mask_c)
    m_n, l_n, p_n = _partial_softmax(s_n, jnp.ones_like(s_n, bool))
    ctx_c = jnp.einsum("bshl,blr->bshr", p_c, cache["ckv"].astype(jnp.float32))
    ctx_n = jnp.einsum("bshl,blr->bshr", p_n, ckv_n.astype(jnp.float32))
    m = jnp.maximum(m_c, m_n)
    c_c, c_n = jnp.exp(m_c - m), jnp.exp(m_n - m)
    l = l_c * c_c + l_n * c_n
    ctx = (ctx_c * c_c[..., None] + ctx_n * c_n[..., None]) / jnp.maximum(
        l[..., None], 1e-20)
    # project latent context through W_uv per head
    out = jnp.einsum("bshr,rhd->bshd", ctx.astype(x1.dtype), params["w_uv"])
    out = out.reshape(B, 1, cfg.n_heads * cfg.v_head_dim)
    return out @ params["wo"], new_cache


# --------------------------------------------------------------------------- #
# Cross attention (whisper decoder)
# --------------------------------------------------------------------------- #
def cross_attn_skeleton(cfg: ModelConfig):
    return attn_skeleton(cfg, ATTN_GLOBAL, cross=True)


def cross_attention(params, cfg: ModelConfig, x, enc_kv: Dict):
    """x: (B,S,D); enc_kv: {"k","v"} (B,F,Hkv,hd) precomputed from encoder."""
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    kf = _repeat_kv(enc_kv["k"], cfg.n_heads)
    vf = _repeat_kv(enc_kv["v"], cfg.n_heads)
    F = kf.shape[1]
    out = online_attention(q, kf, vf, jnp.arange(S), jnp.arange(F),
                           causal=False)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return out @ params["wo"]


def encode_cross_kv(params, cfg: ModelConfig, enc_out):
    B, F, _ = enc_out.shape
    k = (enc_out @ params["wk"]).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ params["wv"]).reshape(B, F, cfg.n_kv_heads, cfg.head_dim)
    return {"k": k, "v": v}
