"""Shared primitive layers: norms, activations, MLPs, RoPE, embeddings.

Everything is a pure function over explicit param pytrees. Param *skeletons*
(pytrees of jax.ShapeDtypeStruct) are the single source of truth for shapes;
`init_params` materializes them with deterministic per-leaf PRNG streams.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# Skeleton / init plumbing
# --------------------------------------------------------------------------- #
def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), jnp.dtype(dtype))


def init_params(skeleton, key) -> Params:
    """Materialize a skeleton with fan-in-scaled normal init.

    Each leaf gets an independent stream derived from a CRC of its tree
    path, so adding/removing params never reshuffles other leaves (important
    for checkpoint-compatible config evolution). The CRC, unlike the salted
    built-in `hash`, is the same in every process: one key gives one set of
    weights everywhere."""
    leaves = jax.tree_util.tree_leaves_with_path(skeleton)

    def one(path, leaf):
        path_str = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib.crc32(path_str.encode()) % (2**31))
        name = path_str.rsplit("'", 2)[-2] if "'" in path_str else path_str
        if leaf.ndim == 0:
            return jnp.zeros((), leaf.dtype)
        if name.startswith(("ln", "norm", "scale")) or name.endswith("scale"):
            return jnp.ones(leaf.shape, leaf.dtype)
        if name in ("bias", "b") or name.endswith("_bias"):
            return jnp.zeros(leaf.shape, leaf.dtype)
        fan_in = leaf.shape[-2] if leaf.ndim >= 2 else leaf.shape[-1]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(k, leaf.shape, jnp.float32) * std).astype(leaf.dtype)

    flat = [one(p, l) for p, l in leaves]
    treedef = jax.tree_util.tree_structure(skeleton)
    return jax.tree_util.tree_unflatten(treedef, flat)


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #
def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


def nonparametric_ln(x, eps: float = 1e-5):
    """OLMo-style LayerNorm without learnable scale/bias."""
    return layernorm(x, None, None, eps)


def norm_skeleton(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "nonparametric_ln":
        return {}  # no params
    return {"scale": sds((d,), cfg.dtype)}


def apply_norm(params, cfg, x):
    if cfg.norm == "nonparametric_ln":
        return nonparametric_ln(x)
    if cfg.norm == "layernorm":
        return layernorm(x, params["scale"])
    return rmsnorm(x, params["scale"])


# --------------------------------------------------------------------------- #
# Activations / MLP
# --------------------------------------------------------------------------- #
def activation(cfg, x):
    if cfg.activation == "gelu":
        return jax.nn.gelu(x)
    if cfg.activation == "squared_relu":
        r = jax.nn.relu(x)
        return r * r
    return jax.nn.silu(x)


def mlp_skeleton(cfg, d_in=None, d_ff=None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    sk = {"wi": sds((d, f), cfg.dtype), "wo": sds((f, d), cfg.dtype)}
    if cfg.gated_mlp:
        sk["wg"] = sds((d, f), cfg.dtype)
    return sk


def apply_mlp(params, cfg, x):
    h = x @ params["wi"]
    if cfg.gated_mlp:
        h = activation(cfg, x @ params["wg"]) * h
    else:
        h = activation(cfg, h)
    return h @ params["wo"]


# --------------------------------------------------------------------------- #
# Rotary embeddings
# --------------------------------------------------------------------------- #
def rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D) or (B, S, D); positions: (S,) int32."""
    dim = x.shape[-1]
    inv = rope_freqs(dim, theta)  # (D/2,)
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 4:  # head axis present: (S, 1, D/2) broadcasts over B, H
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int, dtype):
    """Whisper-style fixed sinusoidal embeddings (S, D)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim * math.log(10000.0))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# --------------------------------------------------------------------------- #
# Embedding / unembedding
# --------------------------------------------------------------------------- #
def embed_skeleton(cfg):
    sk = {"w": sds((cfg.padded_vocab, cfg.d_model), cfg.dtype)}
    return sk


def embed(params, cfg, tokens):
    return jnp.take(params["w"], tokens, axis=0) * math.sqrt(cfg.d_model)


def unembed_skeleton(cfg):
    if cfg.tie_embeddings:
        return {}
    return {"w": sds((cfg.d_model, cfg.padded_vocab), cfg.dtype)}


def unembed(params, embed_params, cfg, h):
    if cfg.tie_embeddings:
        return h @ embed_params["w"].T
    return h @ params["w"]
