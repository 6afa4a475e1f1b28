"""Model facade: one object per ModelConfig exposing skeleton/init and the
three program entry points the framework lowers — train hidden states,
prefill, and single-token decode — uniformly across all families."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec, transformer
from .config import ModelConfig
from .layers import init_params, sds


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ----- params ------------------------------------------------------------
    def skeleton(self) -> Dict[str, Any]:
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_skeleton(self.cfg)
        return transformer.lm_skeleton(self.cfg)

    def init(self, key) -> Dict[str, Any]:
        return init_params(self.skeleton(), key)

    def n_params(self) -> int:
        return sum(int(jnp.prod(jnp.array(l.shape)))
                   for l in jax.tree_util.tree_leaves(self.skeleton()))

    # ----- caches ------------------------------------------------------------
    def cache_skeleton(self, batch: int, ctx: int) -> Dict[str, Any]:
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_cache_skeleton(self.cfg, batch, ctx)
        return transformer.lm_cache_skeleton(self.cfg, batch, ctx)

    def init_cache(self, batch: int, ctx: int) -> Dict[str, Any]:
        return jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, l.dtype),
            self.cache_skeleton(batch, ctx))

    # ----- programs ----------------------------------------------------------
    def hidden(self, params, tokens, *, frontend_embeds=None,
               remat: bool = False):
        """Training forward -> post-norm hidden states (B,S,D)."""
        if self.cfg.is_encoder_decoder:
            h, _ = encdec.encdec_hidden(params, self.cfg, tokens,
                                        frontend_embeds=frontend_embeds,
                                        remat=remat)
            return h
        h, _ = transformer.lm_hidden(params, self.cfg, tokens, mode="train",
                                     frontend_embeds=frontend_embeds,
                                     remat=remat)
        return h

    def logits(self, params, hidden):
        return transformer.lm_logits(params, self.cfg, hidden)

    def prefill(self, params, tokens, *, caches=None, start_pos: int = 0,
                frontend_embeds=None, kv_lens=None, prefix_start=None,
                logits_at=None, attention_impl: str = "xla"):
        """(logits (B,V), caches_out). caches=None: fresh turn-1 prefill;
        otherwise append-prefill against the cached prefix. See lm_prefill
        for the engine-mode prefix_start / logits_at semantics.
        `attention_impl` (static): "pallas" routes fresh global-attention
        prefill through the flash-prefill kernel; families/cases the kernel
        does not cover (MLA, sliding window, append-prefill prefix reads,
        recurrent, encdec) fall back to jnp regardless."""
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_prefill(params, self.cfg, tokens,
                                         frontend_embeds=frontend_embeds,
                                         caches=caches, start_pos=start_pos,
                                         kv_lens=kv_lens)
        return transformer.lm_prefill(params, self.cfg, tokens, caches=caches,
                                      start_pos=start_pos,
                                      frontend_embeds=frontend_embeds,
                                      kv_lens=kv_lens,
                                      prefix_start=prefix_start,
                                      logits_at=logits_at,
                                      attention_impl=attention_impl)

    def decode_step(self, params, token, caches, position, kv_lens=None,
                    ctx_limit=None, attention_impl: str = "xla"):
        """(logits (B,V), cache_updates). Growing caches return the new
        token's entries only; the cache manager appends (DESIGN.md §5).
        `ctx_limit` (static) is an upper bound on kv_lens: attention cache
        reads are trimmed to it (decoder-only path; ignored for encdec).
        `attention_impl` (static): "pallas" serves GQA decode attention
        through the flash-decode kernel; "xla" keeps the jnp path. Families
        the kernel does not cover (MLA, sliding-window, recurrent, encdec)
        fall back to jnp regardless."""
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_decode(params, self.cfg, token, caches,
                                        position, kv_lens=kv_lens)
        return transformer.lm_decode(params, self.cfg, token, caches,
                                     position, kv_lens=kv_lens,
                                     ctx_limit=ctx_limit,
                                     attention_impl=attention_impl)

    def decode_lanes(self, params, token, caches, lens, live, ctx_limit=None):
        """The serving engine's in-place decode step (decoder-only, xla
        attention): (logits (B,V), caches) with the new token's KV written
        into `caches` for the `live` lanes, attention reading only their KV:
        each lane up to its own length where the lane kernel serves, else up
        to `ctx_limit` (static). See `transformer.lm_decode_lanes`."""
        return transformer.lm_decode_lanes(params, self.cfg, token, caches,
                                           lens, live, ctx_limit=ctx_limit)


GROWING_KEYS = ("k", "v", "ckv", "krope")


def merge_decode_cache(caches, updates):
    """Functionally fold one decode step's cache updates into the caches:
    growing entries concatenate along their length axis (grouped trees carry
    a leading layer/group dim); fixed states and cross-attention KV are
    replaced/kept. Used by simple rollout loops; the serving engine uses
    slot buffers instead (repro.engine.kvcache)."""
    if isinstance(caches, dict) and "cross" in caches and \
            "cross" not in (updates or {}):
        updates = {**updates, "cross": caches["cross"]}

    def one(path, c, u):
        names = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
        if names[-1] in GROWING_KEYS and "cross" not in names:
            grouped = names[0] in ("groups", "self")
            ax = 2 if grouped else 1
            return jnp.concatenate([c, u.astype(c.dtype)], axis=ax)
        return u

    return jax.tree_util.tree_map_with_path(one, caches, updates)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
