"""Slot-based KV cache for the real JAX serving engine.

TPU-native adaptation of vLLM's paged KV (DESIGN.md §3): each replica owns
preallocated slot-major cache buffers — slot s is a contiguous max_ctx region
per layer. Contiguous regions suit the TPU's large sequential HBM reads;
page tables have no TPU analogue worth emulating. Conversations pin a slot
for their lifetime (exactly ConServe's binding), lengths are tracked
host-side, and reads beyond a slot's live length are masked via kv_lens.
"""
from __future__ import annotations

import hashlib
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.runtime import (PrefixKVPool, PrefixPoolEntry,  # noqa: F401
                                prefix_eviction_order)
from repro.core.tracing import NULL_TRACER, traced
from repro.models.model import Model

GROWING = ("k", "v", "ckv", "krope")


def _is_growing(path) -> bool:
    names = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    return names[-1] in GROWING and "cross" not in names


@partial(jax.jit, static_argnames=("slot", "length"))
def _write_slot(cache_leaf, new_leaf, slot: int, length: int, grouped: bool):
    """Write a prefilled (batch=1) cache into slot `slot` at [0, length)."""
    if grouped:  # (G, B, L, ...) <- (G, 1, length, ...)
        return jax.lax.dynamic_update_slice(
            cache_leaf, new_leaf.astype(cache_leaf.dtype),
            (0, slot, 0) + (0,) * (cache_leaf.ndim - 3))
    return jax.lax.dynamic_update_slice(
        cache_leaf, new_leaf.astype(cache_leaf.dtype),
        (slot, 0) + (0,) * (cache_leaf.ndim - 2))


def fold_decode_step(caches, updates, lens, mask, grouped, growing):
    """Pure, jit-safe fold of one decode step's cache updates: growing
    entries scatter at each slot's current length (dynamic ``.at[].set``),
    fixed states replace where ``mask`` is set. The decode step of the
    Pallas attention path (and of encoder-decoders) runs it *inside* jit
    after its layer scan, so XLA updates the donated cache buffers in
    place; the xla path writes the same rows inside the layer scan
    (`repro.models.attention.write_rows`).

    ``mask`` is the per-step LIVE mask, not just slot activity: the ragged
    scan passes ``emit & (step < remaining)``, so a slot whose per-slot
    chunk share is exhausted mid-scan stops folding here — its cache row,
    length, and fed-back token are all frozen from that step on while
    longer-running neighbors keep appending. A masked-out slot's row must
    be byte-identical afterwards (tests assert this), which is why every
    branch is a select against the old leaf rather than an unconditional
    write.

    caches/updates: pytrees; lens (n_slots,) int32 device array;
    mask (n_slots,) bool device array; grouped/growing: static bool trees.
    Returns the new caches pytree (same structure/shapes/dtypes)."""
    n_slots = mask.shape[0]

    def fold(cache_leaf, up_leaf, g, gr):
        if gr:
            idx_b = jnp.arange(n_slots)
            if g:  # (G, B, L, ...) <- (G, B, 1, ...)
                return cache_leaf.at[:, idx_b, lens].set(
                    jnp.where(
                        mask.reshape((1, -1) + (1,) * (up_leaf.ndim - 3)),
                        up_leaf[:, :, 0].astype(cache_leaf.dtype),
                        cache_leaf[:, idx_b, lens]))
            return cache_leaf.at[idx_b, lens].set(
                jnp.where(
                    mask.reshape((-1,) + (1,) * (up_leaf.ndim - 2)),
                    up_leaf[:, 0].astype(cache_leaf.dtype),
                    cache_leaf[idx_b, lens]))
        bdim = 1 if g else 0
        shape = [1] * cache_leaf.ndim
        shape[bdim] = n_slots
        return jnp.where(mask.reshape(shape),
                         up_leaf.astype(cache_leaf.dtype), cache_leaf)

    return jax.tree_util.tree_map(fold, caches, updates, grouped, growing)


def slice_slot_prefix(caches, slot, ctx: int, grouped, growing):
    """Pure, jit-safe read of ONE slot's cache rows, with growing entries
    trimmed to the static `ctx` bucket: growing leaves come back as
    (…, 1, ctx, …) views of the slot's prefix region, fixed states as the
    slot's (…, 1, …) row. `slot` may be a traced scalar — this is how the
    AOT-compiled append-prefill program reads its hot prefix *inside* the
    donated jit program, replacing the host-side `export_slot_full` copy
    on the serve path (that method survives as the eager oracle's input).
    Positions at/beyond the slot's live length hold stale bytes; callers
    mask them via kv_lens exactly as with the full-buffer view."""
    def take(leaf, g, gr):
        if gr:
            if g:  # (G, B, L, ...) -> (G, 1, ctx, ...)
                return jax.lax.dynamic_slice(
                    leaf, (0, slot, 0) + (0,) * (leaf.ndim - 3),
                    (leaf.shape[0], 1, min(ctx, leaf.shape[2]))
                    + leaf.shape[3:])
            return jax.lax.dynamic_slice(  # (B, L, ...) -> (1, ctx, ...)
                leaf, (slot, 0) + (0,) * (leaf.ndim - 2),
                (1, min(ctx, leaf.shape[1])) + leaf.shape[2:])
        if g:  # fixed state, grouped: (G, B, ...) -> (G, 1, ...)
            return jax.lax.dynamic_slice(
                leaf, (0, slot) + (0,) * (leaf.ndim - 2),
                (leaf.shape[0], 1) + leaf.shape[2:])
        return jax.lax.dynamic_slice(
            leaf, (slot,) + (0,) * (leaf.ndim - 1), (1,) + leaf.shape[1:])

    return jax.tree_util.tree_map(take, caches, grouped, growing)


def fold_prefill(caches, new_caches, slot, offset, grouped, growing):
    """Pure, jit-safe fold of a (batch=1) prefill result into slot `slot`:
    growing entries land at [offset, offset+S); fixed states replace the
    slot's row. Both `slot` and `offset` may be traced scalars — this is
    the same write `SlotKVCache.write_prefill` performs host-side, hoisted
    into the AOT-compiled prefill program so the donated cache pytree is
    scattered in place (zero host-side KV materialization per prefill).
    The written region may extend past the slot's live length (bucketed
    token padding); reads are masked via kv_lens, exactly as with the
    host-side write."""
    def put(leaf, new_leaf, g, gr):
        new_leaf = new_leaf.astype(leaf.dtype)
        if gr:
            start = ((0, slot, offset) + (0,) * (leaf.ndim - 3) if g
                     else (slot, offset) + (0,) * (leaf.ndim - 2))
        else:
            start = ((0, slot) + (0,) * (leaf.ndim - 2) if g
                     else (slot,) + (0,) * (leaf.ndim - 1))
        return jax.lax.dynamic_update_slice(leaf, new_leaf, start)

    return jax.tree_util.tree_map(put, caches, new_caches, grouped, growing)


class SlotKVCache:
    """Owns the cache pytree (batch dim = n_slots) plus per-slot lengths."""

    tracer = NULL_TRACER  # EngineServer installs its own (core.tracing)

    def __init__(self, model: Model, n_slots: int, max_ctx: int,
                 replica_id: Optional[int] = None):
        self.model = model
        self.cfg = model.cfg
        self.n_slots = n_slots
        self.max_ctx = max_ctx
        self.replica_id = replica_id  # diagnostics only (acquire() error)
        self.caches = model.init_cache(n_slots, max_ctx)
        self.lengths = np.zeros(n_slots, np.int32)
        self.active = np.zeros(n_slots, bool)
        # every leaf under a layer-stacked subtree leads with the layer
        # axis, a (layers, slots, width) recurrent state included
        self._grouped = jax.tree_util.tree_map_with_path(
            lambda p, l: str(getattr(p[0], "key", p[0])) in (
                "groups", "self", "cross"),
            self.caches)
        self._growing = jax.tree_util.tree_map_with_path(
            lambda p, l: _is_growing(p), self.caches)

    # ----- slot management -----------------------------------------------------
    def acquire(self) -> int:
        free = np.flatnonzero(~self.active)
        if len(free) == 0:
            # Unreachable from the serve path: EngineServer admits every
            # slot-holding stage through the per-node admission queue
            # (repro.core.runtime) and only acquires after _can_admit saw a
            # free slot. Kept loud for direct misuse of the cache API.
            who = "?" if self.replica_id is None else self.replica_id
            raise RuntimeError(
                f"no free KV slots on replica {who}: "
                f"{int(self.active.sum())}/{self.n_slots} slots active, "
                f"{self.active_kv_tokens} live KV tokens; serve-path callers "
                f"must wait in the node's admission queue instead of "
                f"acquiring directly")
        s = int(free[0])
        self.active[s] = True
        self.lengths[s] = 0
        return s

    def release(self, slot: int):
        self.active[slot] = False
        self.lengths[slot] = 0

    def invalidate_all(self):
        """Replica failure: every slot's contents are gone at once. Host
        bookkeeping zeroes so the observables mirror the dead cache (strict
        accounting keeps checking dead replicas); the device buffers stay
        allocated — stale bytes on a dead replica are unreachable, and a
        revived replica would re-prefill before any read."""
        self.active[:] = False
        self.lengths[:] = 0

    @property
    def active_kv_tokens(self) -> int:
        return int(self.lengths[self.active].sum())

    def kv_lens(self) -> jnp.ndarray:
        return jnp.asarray(self.lengths)

    # ----- writes ----------------------------------------------------------------
    def write_prefill(self, slot: int, new_caches, length: int,
                      state_slot_batch1: bool = True):
        """Install a (batch=1) prefill result into `slot`: growing entries
        are copied into [0(or prev_len), ...); fixed states replace the slot's
        row. `length` = the slot's total live length afterwards. Host-side
        dispatch of the same `fold_prefill` the AOT prefill programs run
        in-program (this path is the eager oracle's write)."""
        prev = int(self.lengths[slot])
        self.caches = fold_prefill(self.caches, new_caches, slot, prev,
                                   self._grouped, self._growing)
        self.lengths[slot] = length

    # ----- transfer --------------------------------------------------------------
    @traced("engine.kv_export")
    def export_slot(self, slot: int) -> Dict[str, Any]:
        """Extract one slot's live cache (for KV transfer between replicas).
        With tracing on, the call waits for the copy, so its span measures
        the move."""
        length = int(self.lengths[slot])

        def take(path, leaf, grouped, growing):
            if growing:
                return (leaf[:, slot: slot + 1, :length] if grouped
                        else leaf[slot: slot + 1, :length])
            return (leaf[:, slot: slot + 1] if grouped
                    else leaf[slot: slot + 1])

        tree = jax.tree_util.tree_map_with_path(
            lambda p, l, g, gr: take(p, l, g, gr),
            self.caches, self._grouped, self._growing)
        package = {"caches": tree, "length": length}
        if self.tracer.enabled:
            jax.block_until_ready(tree)
            self.tracer.note(bytes=self.nbytes_of(package), length=length)
        return package

    @traced("engine.kv_import")
    def import_slot(self, slot: int, package: Dict[str, Any]):
        """Write an exported slot into `slot` (waited for when tracing, as
        `export_slot`)."""
        self.write_prefill(slot, package["caches"], package["length"])
        if self.tracer.enabled:
            jax.block_until_ready(self.caches)
            self.tracer.note(bytes=self.nbytes_of(package),
                             length=package["length"])

    def export_slot_full(self, slot: int):
        """Full-buffer prefix view of a slot (right-padded beyond the live
        length; callers mask with kv_lens + prefix_start=0)."""
        def take(path, leaf, grouped, growing):
            return leaf[:, slot:slot + 1] if grouped else leaf[slot:slot + 1]

        return jax.tree_util.tree_map_with_path(
            lambda p_, l, g, gr: take(p_, l, g, gr),
            self.caches, self._grouped, self._growing)

    def nbytes_of(self, package) -> int:
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(package["caches"]))


# ----- prefix KV pool ---------------------------------------------------------
def prefix_hash(tokens: Sequence[int]) -> str:
    """Content hash of a token prefix — the pool key. Hashing the TOKENS
    (not a trace-level preamble id) means two conversations share pooled
    rows iff their prefix bytes are actually identical; a workload that
    lies about its preamble identity cannot poison another conversation's
    context."""
    arr = np.asarray(tokens, np.int32)
    return hashlib.blake2b(arr.tobytes(), digest_size=16).hexdigest()


# `PrefixKVPool` / `PrefixPoolEntry` (the node-level pool container both
# backends share) live in repro.core.runtime next to the eviction rule and
# are re-exported above: engine code keeps importing them from here, where
# the device-row lifecycle (materialize / fold / invalidate) is implemented.
