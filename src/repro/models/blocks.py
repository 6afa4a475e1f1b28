"""Per-layer blocks: (norm -> sequence mixer -> residual) + (norm -> FFN ->
residual), specialized by layer kind. One function pair (skeleton/apply) keyed
by kind keeps the grouped layer-scan in transformer.py homogeneous."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (attn_skeleton, gqa_decode, gqa_decode_lanes,
                        gqa_prefill, mla_decode, mla_prefill, write_rows)
from .config import (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA, RGLRU, RWKV6,
                     ModelConfig)
from .layers import apply_mlp, apply_norm, mlp_skeleton, norm_skeleton, sds
from .moe import apply_moe, moe_skeleton
from .recurrent import (rglru_decode, rglru_init_state, rglru_prefill,
                        rglru_skeleton, rwkv6_decode, rwkv6_init_state,
                        rwkv6_prefill, rwkv6_skeleton, rwkv_cmix,
                        rwkv_cmix_skeleton)

ATTN_KINDS = (ATTN_GLOBAL, ATTN_LOCAL, ATTN_MLA)


def block_skeleton(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    sk: Dict[str, Any] = {"ln1": norm_skeleton(cfg), "ln2": norm_skeleton(cfg)}
    if kind in ATTN_KINDS:
        sk["attn"] = attn_skeleton(cfg, kind)
    elif kind == RWKV6:
        sk["tmix"] = rwkv6_skeleton(cfg)
    elif kind == RGLRU:
        sk["rglru"] = rglru_skeleton(cfg)
    else:
        raise ValueError(kind)
    if kind == RWKV6:
        sk["cmix"] = rwkv_cmix_skeleton(cfg)
    elif cfg.n_experts:
        sk["moe"] = moe_skeleton(cfg)
    else:
        sk["mlp"] = mlp_skeleton(cfg)
    return sk


def block_cache_skeleton(cfg: ModelConfig, kind: str, batch: int,
                         ctx: int) -> Dict[str, Any]:
    """Shape skeleton of the decode-time cache one layer of `kind` holds after
    `ctx` tokens. Attention caches grow; recurrent states are fixed-size."""
    hd = cfg.head_dim
    cdt = cfg.kv_cache_dtype or cfg.dtype
    if kind == ATTN_GLOBAL:
        return {"k": sds((batch, ctx, cfg.n_kv_heads, hd), cdt),
                "v": sds((batch, ctx, cfg.n_kv_heads, hd), cdt)}
    if kind == ATTN_LOCAL:
        w = min(ctx, cfg.window) if cfg.window else ctx
        return {"k": sds((batch, w, cfg.n_kv_heads, hd), cdt),
                "v": sds((batch, w, cfg.n_kv_heads, hd), cdt)}
    if kind == ATTN_MLA:
        return {"ckv": sds((batch, ctx, cfg.kv_lora_rank), cdt),
                "krope": sds((batch, ctx, cfg.qk_rope_dim), cdt)}
    if kind == RWKV6:
        hs = cfg.rwkv_head_size
        nh_pad = cfg.rwkv_pad_heads_to or (cfg.d_model // hs)
        return {"s": sds((batch, nh_pad, hs, hs), "float32"),
                "shift": sds((batch, 1, cfg.d_model), cfg.dtype),
                "cshift": sds((batch, 1, cfg.d_model), cfg.dtype)}
    if kind == RGLRU:
        return {"h": sds((batch, cfg.lru_width), "float32"),
                "conv": sds((batch, cfg.conv1d_width - 1, cfg.lru_width),
                            cfg.dtype)}
    raise ValueError(kind)


GROWING_KEYS = ("k", "v", "ckv", "krope")


def is_growing(kind: str) -> bool:
    return kind in ATTN_KINDS


def _ffn(params, cfg: ModelConfig, kind: str, x, cache, updates):
    if kind == RWKV6:
        prev = cache["cshift"] if cache is not None else jnp.zeros(
            (x.shape[0], 1, x.shape[-1]), x.dtype)
        out, cshift = rwkv_cmix(params["cmix"], cfg, x, prev)
        updates["cshift"] = cshift
        return out
    if cfg.n_experts:
        return apply_moe(params["moe"], cfg, x)
    return apply_mlp(params["mlp"], cfg, x)


def block_prefill(params, cfg: ModelConfig, kind: str, x, start_pos,
                  cache: Optional[Dict] = None, kv_lens=None,
                  prefix_start=None, attention_impl: str = "xla"
                  ) -> Tuple[jnp.ndarray, Dict]:
    """cache: prefix KV (append-prefill) or recurrent state; None = fresh.
    Returns (x_out, cache_out): new-token KV entries for attention kinds,
    updated state for recurrent kinds (plus cmix shift under 'cshift').
    `attention_impl` (static) selects the prefill attention kernel for
    global-attention blocks; MLA, sliding-window and recurrent kinds have
    no Pallas prefill kernel and ignore it."""
    updates: Dict[str, Any] = {}
    with jax.named_scope("norm"):
        h = apply_norm(params["ln1"], cfg, x)
    # "attn" holds the sequence mixer of every kind, residual included
    with jax.named_scope("attn"):
        if kind == ATTN_MLA:
            out, cache_out = mla_prefill(params["attn"], cfg, h, start_pos,
                                         prefix_kv=cache, kv_lens=kv_lens,
                                         prefix_start=prefix_start)
        elif kind in (ATTN_GLOBAL, ATTN_LOCAL):
            out, cache_out = gqa_prefill(params["attn"], cfg, kind, h,
                                         start_pos, prefix_kv=cache,
                                         kv_lens=kv_lens,
                                         prefix_start=prefix_start,
                                         attention_impl=attention_impl)
        elif kind == RWKV6:
            state = cache or rwkv6_init_state(cfg, x.shape[0])
            out, cache_out = rwkv6_prefill(
                params["tmix"], cfg, h,
                {"s": state["s"], "shift": state["shift"]})
        elif kind == RGLRU:
            state = cache or rglru_init_state(cfg, x.shape[0])
            out, cache_out = rglru_prefill(params["rglru"], cfg, h, state)
        else:
            raise ValueError(kind)
        x = x + out
    with jax.named_scope("norm"):
        h2 = apply_norm(params["ln2"], cfg, x)
    with jax.named_scope("mlp"):
        x = x + _ffn(params, cfg, kind, h2, cache, updates)
    cache_out = {**cache_out, **updates}
    return x, cache_out


def block_decode(params, cfg: ModelConfig, kind: str, x1, position,
                 cache: Dict, kv_lens=None, ctx_limit: Optional[int] = None,
                 attention_impl: str = "xla") -> Tuple[jnp.ndarray, Dict]:
    """x1: (B,1,D). Returns (x_out, cache_updates): for attention kinds the
    new token's KV entries (engine appends); for recurrent kinds the updated
    state. `ctx_limit` (static upper bound on kv_lens) trims attention cache
    reads; recurrent state is fixed-size and unaffected. `attention_impl`
    (static) selects the GQA decode attention kernel; MLA and recurrent
    kinds have no Pallas decode kernel and ignore it."""
    updates: Dict[str, Any] = {}
    with jax.named_scope("norm"):
        h = apply_norm(params["ln1"], cfg, x1)
    with jax.named_scope("attn"):
        if kind == ATTN_MLA:
            out, cache_out = mla_decode(params["attn"], cfg, h, position,
                                        cache, kv_lens=kv_lens,
                                        ctx_limit=ctx_limit)
        elif kind in (ATTN_GLOBAL, ATTN_LOCAL):
            out, cache_out = gqa_decode(params["attn"], cfg, kind, h, position,
                                        cache, kv_lens=kv_lens,
                                        ctx_limit=ctx_limit,
                                        attention_impl=attention_impl)
        else:
            out, cache_out = _recurrent_decode(params, cfg, kind, h, cache)
        x1 = x1 + out
    with jax.named_scope("norm"):
        h2 = apply_norm(params["ln2"], cfg, x1)
    with jax.named_scope("mlp"):
        x1 = x1 + _ffn(params, cfg, kind, h2, cache, updates)
    cache_out = {**cache_out, **updates}
    return x1, cache_out


def _recurrent_decode(params, cfg: ModelConfig, kind: str, h, state: Dict):
    if kind == RWKV6:
        return rwkv6_decode(params["tmix"], cfg, h,
                            {"s": state["s"], "shift": state["shift"]})
    if kind == RGLRU:
        return rglru_decode(params["rglru"], cfg, h, state)
    raise ValueError(kind)


def block_decode_lanes(params, cfg: ModelConfig, kind: str, x1, cache: Dict,
                       at: Tuple, lens, live, ctx_limit: Optional[int] = None
                       ) -> Tuple[jnp.ndarray, Dict]:
    """One layer of the engine's in-place decode step (xla attention).
    `cache` holds the layer's growing leaves layer-stacked, indexed down to
    this layer by `at`, and its fixed-size states as this layer's slice.
    Growing leaves take the new token's row in place at (*at, slot,
    lens[slot]); global and local GQA read only the live lanes' KV (up to
    each lane's length through the lane kernel, else up to `ctx_limit`),
    MLA its batched trimmed read of this layer. Fixed states
    come back replaced for live lanes. A lane that is not live keeps its
    rows and states byte for byte. Returns (x_out, cache_out)."""
    updates: Dict[str, Any] = {}
    with jax.named_scope("norm"):
        h = apply_norm(params["ln1"], cfg, x1)
    with jax.named_scope("attn"):
        if kind in (ATTN_GLOBAL, ATTN_LOCAL):
            out, cache_out = gqa_decode_lanes(params["attn"], cfg, kind, h,
                                              cache, at, lens, live,
                                              ctx_limit=ctx_limit)
        elif kind == ATTN_MLA:
            out, rows = mla_decode(params["attn"], cfg, h, lens,
                                   {n: l[at] for n, l in cache.items()},
                                   kv_lens=lens, ctx_limit=ctx_limit)
            cache_out = write_rows(cache, rows, at, lens, live)
        else:
            out, cache_out = _recurrent_decode(params, cfg, kind, h, cache)
        x1 = x1 + out
    with jax.named_scope("norm"):
        h2 = apply_norm(params["ln2"], cfg, x1)
    with jax.named_scope("mlp"):
        x1 = x1 + _ffn(params, cfg, kind, h2, cache, updates)
    states = {n: jnp.where(live.reshape((-1,) + (1,) * (u.ndim - 1)),
                           u.astype(cache[n].dtype), cache[n])
              for n, u in {**cache_out, **updates}.items()
              if n not in GROWING_KEYS}
    return x1, {**cache_out, **states}
