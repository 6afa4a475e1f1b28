"""Decoder-only LM assembled from a ModelConfig: scan over layer *pattern
groups* (HLO size ~O(1) in depth), optional stub frontend (VLM), remat in
train mode, and the prefill / decode entry points the serving engine uses."""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .blocks import (GROWING_KEYS, block_cache_skeleton, block_decode,
                     block_decode_lanes, block_prefill, block_skeleton)
from .config import ModelConfig
from .layers import (apply_norm, embed, embed_skeleton, norm_skeleton, sds,
                     unembed, unembed_skeleton)


def _stack_skeleton(sk, n: int):
    return jax.tree_util.tree_map(
        lambda l: sds((n, *l.shape), l.dtype), sk)


def lm_skeleton(cfg: ModelConfig) -> Dict[str, Any]:
    pat, n_groups, rem = cfg.pattern_groups()
    sk: Dict[str, Any] = {
        "embed": embed_skeleton(cfg),
        "final_norm": norm_skeleton(cfg),
        "unembed": unembed_skeleton(cfg),
    }
    if n_groups:
        gsk = {f"p{i}": block_skeleton(cfg, kind) for i, kind in enumerate(pat)}
        sk["groups"] = _stack_skeleton(gsk, n_groups)
    if rem:
        sk["rem"] = {f"p{i}": block_skeleton(cfg, kind)
                     for i, kind in enumerate(rem)}
    return sk


def lm_cache_skeleton(cfg: ModelConfig, batch: int, ctx: int) -> Dict[str, Any]:
    pat, n_groups, rem = cfg.pattern_groups()
    ck: Dict[str, Any] = {}
    if n_groups:
        gck = {f"p{i}": block_cache_skeleton(cfg, kind, batch, ctx)
               for i, kind in enumerate(pat)}
        ck["groups"] = _stack_skeleton(gck, n_groups)
    if rem:
        ck["rem"] = {f"p{i}": block_cache_skeleton(cfg, kind, batch, ctx)
                     for i, kind in enumerate(rem)}
    return ck


def _embed_inputs(params, cfg: ModelConfig, tokens, frontend_embeds):
    h = embed(params["embed"], cfg, tokens).astype(cfg.jnp_dtype)
    n_front = 0
    if cfg.frontend != "none" and frontend_embeds is not None:
        fe = frontend_embeds.astype(cfg.jnp_dtype)
        h = jnp.concatenate([fe, h], axis=1)
        n_front = fe.shape[1]
    return h, n_front


def lm_hidden(params, cfg: ModelConfig, tokens, *, mode: str = "train",
              caches: Optional[Dict] = None, start_pos: int = 0,
              frontend_embeds=None, kv_lens=None, remat: bool = False,
              prefix_start=None, attention_impl: str = "xla"
              ) -> Tuple[jnp.ndarray, Dict]:
    """Run the stack in 'train'/'prefill' mode. Returns (hidden, caches_out).
    hidden is post-final-norm (B, S[, +frontend], D); caller unembeds
    (train uses chunked-vocab loss instead of materializing logits).
    `attention_impl` (static) selects the prefill attention kernel for
    global-attention blocks (see gqa_prefill); the train path keeps the
    default jnp attention."""
    pat, n_groups, rem = cfg.pattern_groups()
    with jax.named_scope("embed"):
        h, n_front = _embed_inputs(params, cfg, tokens, frontend_embeds)
    sp = start_pos  # frontend tokens occupy the first positions

    def one_block(kind, bparams, hh, bcache):
        return block_prefill(bparams, cfg, kind, hh, sp, cache=bcache,
                             kv_lens=kv_lens, prefix_start=prefix_start,
                             attention_impl=attention_impl)

    per_layer = remat and cfg.remat_granularity in ("layer", "both")
    block_fns = {kind: (jax.checkpoint(partial(one_block, kind))
                        if per_layer else partial(one_block, kind))
                 for kind in set(pat)}

    train_mode = mode == "train"

    def group_fn(hc, xs):
        gparams, gcache = xs
        hh = hc
        outs = {}
        for i, kind in enumerate(pat):
            key = f"p{i}"
            hh, co = block_fns[kind](
                gparams[key], hh,
                None if gcache is None else gcache[key])
            if not train_mode:
                outs[key] = co
        return hh, outs

    outer = remat and cfg.remat_granularity in ("group", "both")
    body = jax.checkpoint(group_fn) if outer else group_fn
    caches_out: Dict[str, Any] = {}
    with jax.named_scope("layers"):
        if n_groups:
            gcaches = None if caches is None else caches["groups"]
            if cfg.unroll_layers:
                outs = []
                for gi in range(n_groups):
                    gp = jax.tree_util.tree_map(lambda l: l[gi],
                                                params["groups"])
                    gc = None if gcaches is None else jax.tree_util.tree_map(
                        lambda l: l[gi], gcaches)
                    h, o = body(h, (gp, gc))
                    outs.append(o)
                gouts = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                               *outs)
            elif gcaches is None:
                # scan can't carry a None xs leaf; close over it instead
                def body_nocache(hc, gparams):
                    return body(hc, (gparams, None))
                h, gouts = jax.lax.scan(body_nocache, h, params["groups"])
            else:
                h, gouts = jax.lax.scan(lambda hc, x: body(hc, x), h,
                                        (params["groups"], gcaches))
            caches_out["groups"] = gouts
        if rem:
            routs = {}
            for i, kind in enumerate(rem):
                key = f"p{i}"
                rc = None if caches is None else caches["rem"][key]
                h, co = block_prefill(params["rem"][key], cfg, kind, h, sp,
                                      cache=rc, kv_lens=kv_lens,
                                      prefix_start=prefix_start,
                                      attention_impl=attention_impl)
                if not train_mode:
                    routs[key] = co
            caches_out["rem"] = routs
    with jax.named_scope("norm"):
        h = apply_norm(params["final_norm"], cfg, h)
    if n_front:
        h = h[:, n_front:]
    return h, caches_out


def lm_logits(params, cfg: ModelConfig, hidden):
    return unembed(params.get("unembed", {}), params["embed"], cfg, hidden)


def lm_prefill(params, cfg: ModelConfig, tokens, *, caches=None,
               start_pos: int = 0, frontend_embeds=None, kv_lens=None,
               prefix_start=None, logits_at=None, attention_impl: str = "xla"):
    """Prefill: returns (logits (B,V), caches_out). logits_at selects the
    position whose logits are returned (engine passes true_len-1 when the
    token batch is right-padded to a bucket; default: last position).
    `attention_impl` (static) selects the prefill attention kernel."""
    h, caches_out = lm_hidden(params, cfg, tokens, mode="prefill",
                              caches=caches, start_pos=start_pos,
                              frontend_embeds=frontend_embeds, kv_lens=kv_lens,
                              prefix_start=prefix_start,
                              attention_impl=attention_impl)
    with jax.named_scope("logits"):
        if logits_at is None:
            hh = h[:, -1]
        else:
            idx = jnp.asarray(logits_at, jnp.int32)
            if idx.ndim == 0:
                hh = jax.lax.dynamic_index_in_dim(h, idx, axis=1,
                                                  keepdims=False)
            else:  # per-sequence gather
                hh = jnp.take_along_axis(
                    h, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return lm_logits(params, cfg, hh), caches_out


def lm_decode(params, cfg: ModelConfig, token, caches, position,
              kv_lens=None, ctx_limit=None, attention_impl: str = "xla"):
    """One decode step. token: (B,) int32; caches as from lm_cache_skeleton.
    Returns (logits (B,V), cache_updates) — attention updates are the new
    token's KV entries only (DESIGN.md §5). `ctx_limit` (static int) is an
    upper bound on kv_lens used to trim attention cache reads;
    `attention_impl` (static) selects the GQA decode attention kernel."""
    pat, n_groups, rem = cfg.pattern_groups()
    with jax.named_scope("embed"):
        h = embed(params["embed"], cfg, token[:, None]).astype(cfg.jnp_dtype)

    updates: Dict[str, Any] = {}
    with jax.named_scope("layers"):
        if n_groups:
            def group_fn(hc, xs):
                gparams, gcache = xs
                hh = hc
                outs = {}
                for i, kind in enumerate(pat):
                    key = f"p{i}"
                    hh, up = block_decode(gparams[key], cfg, kind, hh,
                                          position, gcache[key],
                                          kv_lens=kv_lens,
                                          ctx_limit=ctx_limit,
                                          attention_impl=attention_impl)
                    outs[key] = up
                return hh, outs

            if cfg.unroll_layers:
                outs = []
                for gi in range(n_groups):
                    gp = jax.tree_util.tree_map(lambda l: l[gi],
                                                params["groups"])
                    gc = jax.tree_util.tree_map(lambda l: l[gi],
                                                caches["groups"])
                    h, o = group_fn(h, (gp, gc))
                    outs.append(o)
                gups = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                              *outs)
            else:
                h, gups = jax.lax.scan(group_fn, h,
                                       (params["groups"], caches["groups"]))
            updates["groups"] = gups
        if rem:
            rups = {}
            for i, kind in enumerate(rem):
                key = f"p{i}"
                h, up = block_decode(params["rem"][key], cfg, kind, h,
                                     position, caches["rem"][key],
                                     kv_lens=kv_lens,
                                     ctx_limit=ctx_limit,
                                     attention_impl=attention_impl)
                rups[key] = up
            updates["rem"] = rups
    with jax.named_scope("norm"):
        h = apply_norm(params["final_norm"], cfg, h)
    with jax.named_scope("logits"):
        return lm_logits(params, cfg, h[:, 0]), updates


def _split_growing(leaves: Dict[str, Any]):
    """One block's cache -> (its growing leaves, its fixed states)."""
    return ({n: l for n, l in leaves.items() if n in GROWING_KEYS},
            {n: l for n, l in leaves.items() if n not in GROWING_KEYS})


def lm_decode_lanes(params, cfg: ModelConfig, token, caches, lens, live,
                    ctx_limit=None):
    """The engine's in-place decode step (xla attention): one token for every
    lane, its KV written into `caches` inside the layer scan, attention
    reading only the `live` lanes' KV straight from the layer-stacked
    leaves: each lane up to its own length where the lane kernel serves
    (`attention.lane_kernel_serves`), else up to `ctx_limit` (static). The
    scan carries the growing leaves
    instead of slicing them as its xs, so no layer's slab spanning several
    lanes is ever materialized; fixed-size states are sliced per layer as
    in `lm_decode`. `lens` (B,) is each lane's length and position. A lane
    that is not live keeps its cache rows and states byte for byte (its
    logits are computed from a zero attention output and mean nothing).
    The layer loop is always a scan (`cfg.unroll_layers` serves the dry
    run's cost analysis of `lm_decode`). Returns (logits (B,V), caches)."""
    pat, n_groups, rem = cfg.pattern_groups()
    with jax.named_scope("embed"):
        h = embed(params["embed"], cfg, token[:, None]).astype(cfg.jnp_dtype)

    out: Dict[str, Any] = {}
    with jax.named_scope("layers"):
        if n_groups:
            def group_fn(carry, xs):
                hh, grow = carry
                gparams, fixed, layer = xs
                grow, fixed = dict(grow), dict(fixed)
                for i, kind in enumerate(pat):
                    key = f"p{i}"
                    hh, c = block_decode_lanes(
                        gparams[key], cfg, kind, hh,
                        {**grow[key], **fixed[key]}, (layer,), lens, live,
                        ctx_limit=ctx_limit)
                    grow[key], fixed[key] = _split_growing(c)
                return (hh, grow), fixed

            split = {k: _split_growing(c) for k, c in caches["groups"].items()}
            grow = {k: g for k, (g, _) in split.items()}
            fixed = {k: f for k, (_, f) in split.items()}
            (h, grow), fixed = jax.lax.scan(
                group_fn, (h, grow),
                (params["groups"], fixed, jnp.arange(n_groups)))
            out["groups"] = {k: {**grow[k], **fixed[k]} for k in grow}
        if rem:
            out["rem"] = {}
            for i, kind in enumerate(rem):
                key = f"p{i}"
                h, out["rem"][key] = block_decode_lanes(
                    params["rem"][key], cfg, kind, h, caches["rem"][key], (),
                    lens, live, ctx_limit=ctx_limit)
    with jax.named_scope("norm"):
        h = apply_norm(params["final_norm"], cfg, h)
    with jax.named_scope("logits"):
        return lm_logits(params, cfg, h[:, 0]), out
