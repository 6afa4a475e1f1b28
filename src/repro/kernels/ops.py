"""Jit'd public wrappers around the Pallas kernels with backend dispatch:
on TPU the compiled kernels run natively (interpret=False); on CPU they
execute in interpret mode (tests and validation); any other backend raises
rather than interpreting in silence. `impl="xla"` selects the jnp
reference path instead. The model substrate uses the XLA path for
the multi-device dry-run (Pallas inside GSPMD is a per-backend concern);
kernels are selectable via `attention_impl` for single-replica serving,
and the engine's in-place decode reads the live lanes through
`lane_decode_attention` wherever it runs on the TPU
(`models.attention.lane_kernel_serves`)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import flash_decode_attention
from .lane_decode_attention import lane_decode_attention as _lane_decode
from .prefill_attention import flash_prefill_attention
from .rglru_kernel import rglru_pallas
from .rwkv6_kernel import wkv6_pallas


def _interpret() -> bool:
    """Interpret mode for the backend the kernels are traced on: native on
    TPU, interpreted on CPU, refused anywhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run natively on TPU and interpreted on CPU; "
        f"backend {backend!r} is neither — use impl='xla'")


@partial(jax.jit, static_argnames=("window", "impl"))
def prefill_attention(q, k, v, *, window: int = 0, impl: str = "pallas"):
    """q,k,v: (B, S, H, D) — causal (optionally sliding-window) attention."""
    if impl == "xla":
        return ref.causal_attention_ref(q, k, v, window=window)
    out = flash_prefill_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), window=window, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("impl", "max_len"))
def decode_attention(q, k, v, lengths=None, *, impl: str = "pallas",
                     max_len: int | None = None):
    """q: (B,H,D); k,v: (B,S,Hkv,D); lengths: (B,). Flash-decode GQA.
    `max_len` (static) bounds the live lengths so the kernel grid only
    spans the live KV prefix (dead tail blocks are never fetched)."""
    if lengths is None and max_len is not None and max_len < k.shape[1]:
        raise ValueError("max_len < S requires lengths (see "
                         "flash_decode_attention)")
    if impl == "xla":
        if max_len is not None:
            s = min(k.shape[1], -(-int(max_len) // 128) * 128)
            k, v = k[:, :s], v[:, :s]
        return ref.decode_attention_ref(q, k, v, lengths)
    return flash_decode_attention(q, k, v, lengths, max_len=max_len,
                                  interpret=_interpret())


def lane_decode_attention(q, k_new, v_new, k, v, layer, lens, live):
    """One decode layer's attention over the live lanes' KV, read in place
    from the layer-stacked leaves k, v (NL, B, L, Hkv, D) at `layer`, each
    lane up to its own length; the new token's entries combine beside it.
    Returns (B, H, D) float32, zeros for lanes that are not live. Called
    inside the traced decode scan, so it is not jitted on its own."""
    return _lane_decode(q, k_new, v_new, k, v, layer, lens, live,
                        interpret=_interpret())


@partial(jax.jit, static_argnames=("impl", "chunk"))
def wkv6(r, k, v, logw, u, state, *, chunk: int = 32, impl: str = "pallas"):
    """Chunk-parallel WKV6. Returns (y, final_state), both fp32."""
    if impl == "xla":
        return ref.wkv6_ref(r, k, v, logw, u, state)
    return wkv6_pallas(r, k, v, logw, u, state, chunk=chunk,
                       interpret=_interpret())


@partial(jax.jit, static_argnames=("impl", "chunk"))
def rglru_scan(log_a, b, h0, *, chunk: int = 128, impl: str = "pallas"):
    """Gated linear recurrence h_t = exp(log_a_t) h_{t-1} + b_t."""
    if impl == "xla":
        return ref.rglru_ref(log_a, b, h0)
    return rglru_pallas(log_a, b, h0, chunk=chunk, interpret=_interpret())
