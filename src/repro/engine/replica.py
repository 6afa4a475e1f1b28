"""A model replica: params + slot KV cache + jitted prefill/decode programs,
with bucketed prefill lengths (bounded recompilation) and greedy sampling.
Runs real forward passes on whatever devices are visible (CPU here; the same
code paths pjit onto a mesh slice in production).

Decode tail (the paper's memory-bound phase) is served by ONE jitted,
buffer-donated program per chunk bucket, or per (chunk, ctx) bucket where
the read needs a static context bound: `jax.lax.scan` over the
bucketed chunk length with on-device greedy sampling fed back as the next
token and the per-slot cache write fused into the step, so XLA writes the
donated KV buffers in place — no per-token full-cache copy, one dispatch +
one host sync per chunk instead of per token. With xla attention each layer
writes its row and reads only the live lanes' KV straight from the
layer-stacked cache (`Model.decode_lanes`): on the TPU, where every layer
takes the length-bounded Pallas read, each lane up to its own length, with
no ctx bucket; elsewhere through the XLA lane loop up to the ctx bucket.
`attention_impl="pallas"` folds the step's new rows after the layer scan
(`fold_decode_step`).
The scan is RAGGED: `decode_steps` takes a per-slot `remaining` vector
and each slot freezes (stops folding KV, stops advancing its length,
stops consuming tokens) once its own count is exhausted, so a
nearly-finished turn no longer collapses the chunk for the whole batch —
the agentic-trace irregularity the paper's conversation-level view is
meant to absorb. Fused programs are AOT
compiled (`jax.jit(...).lower(...).compile()`): compile time accumulates
in `compile_s` and never pollutes the measured per-chunk `dt` the server
feeds its logical clock and TBT EMA. `decode_step_all_reference` keeps
the one-dispatch-per-token path (host sync, host argmax, a copying cache
write, no ctx trim) over the same step as the parity oracle and benchmark
baseline.

The (append-)prefill path (the paper's compute-bound phase, and the
turn-2+ hot-prefix appends PPD treats as their own latency class) gets
the same architecture: ONE AOT-compiled donated program per length
bucket (turn-1) or (length, prefix-ctx) bucket (append). The forward,
the logits gather at the last live position, greedy sampling, and the
per-slot KV write (a dynamic-slice scatter into the donated slot cache
pytree) all run inside the program — one dispatch, zero host-side KV
materialization, and no `export_slot_full` copy on the append path
(the prefix is a dynamic slice of the slot's own rows trimmed to its
ctx bucket). `prefill_mode="reference"` replays the eager per-op path
as the parity oracle; `warmup_prefill` pre-compiles buckets for cold
replicas, with compile seconds in `compile_s`, never in measured dt."""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tracing import NULL_TRACER, traced
from repro.kernels.lane_decode_attention import lane_block_k
from repro.models import Model, build_model
from repro.models.attention import lanes_length_bounded
from repro.models.config import ModelConfig

from .kvcache import (PrefixKVPool, SlotKVCache, fold_decode_step,
                      fold_prefill, prefix_hash, slice_slot_prefix)

PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)

DECODE_CHUNKS = (1, 2, 4, 8, 16, 32)
CTX_BUCKET_MIN = 64

# Process-wide AOT prefill program cache. A compiled (append-)prefill
# executable is a pure function of (model config, cache geometry,
# attention impl, bucket key) — params and caches are ARGUMENTS — so
# replicas with identical signatures (every multi-replica deployment, and
# every engine a test builds) share one compile instead of each paying
# ~seconds per bucket. compile_s is charged only by the replica that
# actually compiled (a cache hit costs nothing and charges nothing).
_AOT_PREFILL_CACHE: Dict[Tuple, Any] = {}


def bucket_len(n: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return b
    return -(-n // 4096) * 4096


def decode_chunk_bucket(n: int) -> int:
    """Smallest compiled scan length covering n steps (bounds recompiles;
    steps beyond the live count are masked out inside the scan)."""
    for b in DECODE_CHUNKS:
        if n <= b:
            return b
    return DECODE_CHUNKS[-1]


def decode_chunk_floor(n: int) -> int:
    """Largest compiled bucket <= n (floor 1): the chunk size a caller
    should dispatch so the scan runs at exactly its compiled length with no
    masked no-op tail. EngineServer._iterate and the decode_tail benchmark
    both size chunks through this, so policy and replay stay locked
    together."""
    f = 1
    for b in DECODE_CHUNKS:
        if b <= n:
            f = b
    return f


def ctx_bucket(n: int, max_ctx: int) -> int:
    """Power-of-two live-context bucket for the trimmed decode read."""
    b = CTX_BUCKET_MIN
    while b < n:
        b *= 2
    return min(b, max_ctx)


def program_name(kind: str, bucket: int, ctx: Optional[int] = None,
                 n_front: int = 0) -> str:
    """Stable name of one AOT program: `prefill_b<bucket>`,
    `append_b<bucket>_c<ctx>`, `shared_b<bucket>_c<ctx>`,
    `decode_fused_n<steps>_c<ctx>` (`_cNone` where each lane's read is
    bounded by its own length). The compiled module is `jit_<name>`, in
    its HLO text and in a device profile's "XLA Modules" line."""
    if kind == "decode_fused":
        return f"decode_fused_n{bucket}_c{ctx}"
    name = f"{kind}_b{bucket}"
    if ctx is not None:
        name += f"_c{ctx}"
    return name + (f"_f{n_front}" if n_front else "")


def _named(fn, name: str):
    """`fn` under `name`, which jax.jit gives the program it builds."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class ReplicaEngine:
    # spans of this replica's calls (core.tracing); EngineServer installs
    # its own tracer on every replica and slot cache it serves
    tracer = NULL_TRACER

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 max_ctx: int = 2048, replica_id: int = 0, role: str = "decode",
                 warmup: bool = False, attention_impl: str = "xla",
                 prefill_mode: str = "jit", prefix_pool_tokens: int = 0):
        """attention_impl: "xla" (default) serves decode attention through the
        pure-jnp model path on every backend; "pallas" routes GQA decode
        attention through the flash-decode kernel (ops.decode_attention) and
        fresh global-attention prefill through the flash-prefill kernel —
        native on TPU, interpret-mode elsewhere. Threaded statically into the
        jitted programs, so switching never recompiles the jnp path.
        prefill_mode: "jit" (default) serves (append-)prefill through ONE
        AOT-compiled donated program per (length-bucket[, ctx-bucket]) — the
        per-slot KV write is a dynamic-slice scatter INSIDE the program, so
        a prefill is one dispatch with zero host-side KV materialization.
        "reference" replays the eager per-op path (host-side `write_prefill`
        copy; append reads the prefix via `export_slot_full`) — the parity
        oracle and benchmark baseline. Families the jitted path does not
        cover (exact-length recurrent prefill, encoder-decoder) fall back
        to the reference path regardless of the mode.
        prefix_pool_tokens: live-token budget for the node-level prefix KV
        pool (0 = no pool). A turn-1 prefill called with `prefix_len` > 0
        ALWAYS splits at that boundary (the split, not the pool, fixes the
        math — see prefill_conversation); the pool only changes where the
        prefix rows come from: a hit serves them through the fused
        shared-prefix program instead of recomputing them."""
        assert prefill_mode in ("jit", "reference")
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.kv = SlotKVCache(self.model, n_slots, max_ctx,
                              replica_id=replica_id)
        self.replica_id = replica_id
        self.role = role
        self.attention_impl = attention_impl
        self.exact_prefill = any(k in ("rwkv6", "rglru")
                                 for k in cfg.block_pattern)
        self.prefill_mode = prefill_mode
        # recurrent prefill consumes every position (padding would corrupt
        # state -> unbounded exact-length recompiles) and encdec lacks the
        # engine-mode prefill kwargs: both stay on the eager reference path
        self._prefill_jittable = (not self.exact_prefill
                                  and not cfg.is_encoder_decoder)
        self.compute_s = 0.0  # accumulated measured compute time
        self.compile_s = 0.0  # prefill+decode AOT compile time (OUT of dt)
        self.decode_s = 0.0   # decode-only share of compute_s: the
        #                       denominator of EFFECTIVE decode tokens/s
        #                       (n_decode_tokens / decode_s) — masked no-op
        #                       forwards and dispatch overhead both land
        #                       here, so the rotation win is measurable
        self.prefill_s = 0.0  # prefill-only share of compute_s (the
        #                       denominator of prefill tokens/s)
        self.n_prefill_tokens = 0
        self.n_decode_tokens = 0
        # node-level prefix KV pool (None = disabled). Pooled rows are
        # owned by NO slot and never donated: the fused shared-prefix
        # program reads them as a non-donated argument, so one entry can
        # feed any number of prefills while slot caches churn in place.
        self.prefix_pool = (PrefixKVPool(prefix_pool_tokens)
                            if prefix_pool_tokens > 0 else None)
        # prefix tokens served FROM the pool instead of recomputed —
        # the engine-side ground truth behind NodeState.pooled_prefix_hits
        self.n_pooled_prefix_tokens = 0

        # xla attention on a decoder-only stack decodes in place
        # (Model.decode_lanes); the Pallas kernel and encoder-decoders
        # return the step's new rows for fold_decode_step
        self._in_place = (attention_impl == "xla"
                          and not cfg.is_encoder_decoder)
        # ... and where every layer reads through the length-bounded Pallas
        # kernel, the read needs no static ctx bound: programs are keyed by
        # scan length alone (ctx None), and each lane-step reads its length
        # rounded up to the kernel's block
        self._lane_kernel = self._in_place and lanes_length_bounded(cfg)
        self._lane_block = lane_block_k(
            max_ctx, cfg.n_kv_heads, cfg.head_dim,
            jnp.dtype(cfg.jnp_dtype).itemsize)
        self._decode = jax.jit(
            lambda p, t, c, lens, live: self._step(p, t, c, lens, live, None))
        # fused donated decode programs, keyed by (scan length, ctx bucket)
        self._fused: Dict[Tuple[int, Optional[int]], Any] = {}
        if warmup:
            self.warmup_decode()
            if self._prefill_jittable and prefill_mode == "jit":
                self.warmup_prefill()

    # ----- sampling -------------------------------------------------------------
    def sample(self, logits) -> np.ndarray:
        """Greedy over the true vocab (mask table padding)."""
        logits = logits[..., : self.cfg.vocab_size]
        return np.asarray(jnp.argmax(logits, axis=-1), np.int32)

    # ----- prefill ----------------------------------------------------------------
    def _use_jit_prefill(self) -> bool:
        return self.prefill_mode == "jit" and self._prefill_jittable

    def _check_prefill_room(self, slot: int, need: int):
        """The in-slot scatter would clamp at the buffer edge while host
        lengths advance past it — refuse loudly, naming the slot, in BOTH
        prefill modes (mirrors the decode_steps overflow guard)."""
        prev = int(self.kv.lengths[slot])
        if prev + need > self.kv.max_ctx:
            raise RuntimeError(
                f"prefill overflow on replica {self.replica_id}: slot {slot} "
                f"at length {prev} cannot take {need} more tokens "
                f"(max_ctx={self.kv.max_ctx})")

    def _prefill_pad(self, true_len: int, room: int) -> int:
        """Padded token length for a prefill whose slot has `room` positions
        left. Normally the length bucket — but the scatter writes the FULL
        padded region at the slot offset, and `dynamic_update_slice` clamps
        a start that would run off the buffer (silently corrupting the live
        prefix), so a nearly-full slot whose true length fits but whose
        bucket does not falls back to an exact-length program (a one-off
        compile in a regime bucketing cannot serve). Both prefill modes pad
        identically, keeping caches byte-comparable bit for bit."""
        pad = bucket_len(true_len)
        return pad if pad <= room else true_len

    def _build_prefill(self, name: str = "prefill"):
        """Turn-1 prefill program builder (the token bucket and frontend
        shape are fixed by the .lower() specs at the _get_prefill call
        site): forward over the padded bucket, logits gathered at the
        (traced) last live position,
        greedy argmax ON DEVICE, and the per-slot KV write as a donated
        dynamic-slice scatter into the slot cache pytree — one dispatch,
        zero host-side KV materialization. `slot` and `true_len` are traced
        scalars, so one compiled program serves every slot and every true
        length inside the bucket."""
        grouped, growing = self.kv._grouped, self.kv._growing
        vocab = self.cfg.vocab_size

        def run(params, caches, tokens, slot, true_len, fe):
            logits, new = self.model.prefill(
                params, tokens[None], frontend_embeds=fe,
                logits_at=true_len - 1,
                attention_impl=self.attention_impl)
            caches = fold_prefill(caches, new, slot, 0, grouped, growing)
            tok = jnp.argmax(logits[0, :vocab]).astype(jnp.int32)
            return caches, tok

        return jax.jit(_named(run, name), donate_argnums=(1,))

    def _build_append(self, ctx: int, name: Optional[str] = None):
        """Append-prefill program for one prefix ctx bucket (the token
        bucket is fixed by the .lower() specs at the _get_append call site):
        the hot prefix is a dynamic slice of the slot's own cache rows
        trimmed to `ctx` (no host-side `export_slot_full` copy), padding
        past the live length is masked via kv_lens, and the new tokens'
        KV scatters back into the slot at the (traced) previous length —
        the donated in-place contract of the fused decode scan, applied to
        the ConServe fast path."""
        grouped, growing = self.kv._grouped, self.kv._growing
        vocab = self.cfg.vocab_size

        def run(params, caches, tokens, slot, true_len, prev_len):
            prefix = slice_slot_prefix(caches, slot, ctx, grouped, growing)
            lens = jnp.reshape(prev_len.astype(jnp.int32), (1,))
            logits, new = self.model.prefill(
                params, tokens[None], caches=prefix, start_pos=prev_len,
                kv_lens=lens, prefix_start=0, logits_at=true_len - 1,
                attention_impl=self.attention_impl)
            caches = fold_prefill(caches, new, slot, prev_len, grouped,
                                  growing)
            tok = jnp.argmax(logits[0, :vocab]).astype(jnp.int32)
            return caches, tok

        return jax.jit(_named(run, name or f"append_c{ctx}"),
                       donate_argnums=(1,))

    def _aot_specs(self):
        spec = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            jnp.shape(x), x.dtype)
        return (jax.tree_util.tree_map(spec, self.params),
                jax.tree_util.tree_map(spec, self.kv.caches))

    def _prefill_cache_key(self, kind: str, *bucket) -> Tuple:
        """Process-wide cache key: everything the compiled executable is a
        function of besides its runtime arguments. cfg repr covers params
        and cache pytree structure; (n_slots, max_ctx) cover geometry."""
        return (repr(self.cfg), self.kv.n_slots, self.kv.max_ctx,
                self.attention_impl, kind, *bucket)

    def _get_prefill(self, pad_to: int, n_front: int):
        """Fetch (or AOT-compile) the turn-1 program for one token bucket.
        Compile time goes to `self.compile_s`, never into measured dt."""
        key = self._prefill_cache_key("prefill", pad_to, n_front)
        fn = _AOT_PREFILL_CACHE.get(key)
        if fn is None:
            t0 = time.perf_counter()
            pspec, cspec = self._aot_specs()
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            fe_spec = None if not n_front else jax.ShapeDtypeStruct(
                (1, n_front, self.cfg.d_model), self.cfg.jnp_dtype)
            fn = self._build_prefill(
                program_name("prefill", pad_to, n_front=n_front)).lower(
                pspec, cspec, jax.ShapeDtypeStruct((pad_to,), jnp.int32),
                scalar, scalar, fe_spec).compile()
            self.compile_s += time.perf_counter() - t0
            _AOT_PREFILL_CACHE[key] = fn
        return fn

    def _get_append(self, pad_to: int, ctx: int):
        """Fetch (or AOT-compile) the append program for one (token bucket,
        prefix ctx bucket). Compile time goes to `self.compile_s`."""
        key = self._prefill_cache_key("append", pad_to, ctx)
        fn = _AOT_PREFILL_CACHE.get(key)
        if fn is None:
            t0 = time.perf_counter()
            pspec, cspec = self._aot_specs()
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            fn = self._build_append(
                ctx, program_name("append", pad_to, ctx)).lower(
                pspec, cspec, jax.ShapeDtypeStruct((pad_to,), jnp.int32),
                scalar, scalar, scalar).compile()
            self.compile_s += time.perf_counter() - t0
            _AOT_PREFILL_CACHE[key] = fn
        return fn

    def _build_shared(self, ctx: int, name: str):
        """Shared-prefix prefill program for one pooled ctx bucket (the
        delta-token bucket is fixed by the .lower() specs at the _get_shared
        call site) — the third prefill class: append-against-shared-prefix.
        The POOLED rows (a non-donated argument shaped exactly like
        `slice_slot_prefix`'s output) are first scattered into the slot at
        offset 0 — the slot physically holds the full context afterwards,
        same as if it had prefilled the preamble itself — then the delta
        forward reads them back through the SAME `slice_slot_prefix` read
        the append class uses, and the delta's KV scatters in at the traced
        previous length. Byte-equality with the recompute path (turn-1
        program on the preamble + append program on the delta) is a tested
        property, not an aspiration: same reads, same folds, same programs
        downstream."""
        grouped, growing = self.kv._grouped, self.kv._growing
        vocab = self.cfg.vocab_size

        def run(params, caches, pool, tokens, slot, true_len, prev_len):
            caches = fold_prefill(caches, pool, slot, 0, grouped, growing)
            prefix = slice_slot_prefix(caches, slot, ctx, grouped, growing)
            lens = jnp.reshape(prev_len.astype(jnp.int32), (1,))
            logits, new = self.model.prefill(
                params, tokens[None], caches=prefix, start_pos=prev_len,
                kv_lens=lens, prefix_start=0, logits_at=true_len - 1,
                attention_impl=self.attention_impl)
            caches = fold_prefill(caches, new, slot, prev_len, grouped,
                                  growing)
            tok = jnp.argmax(logits[0, :vocab]).astype(jnp.int32)
            return caches, tok

        return jax.jit(_named(run, name), donate_argnums=(1,))

    def _pool_specs(self, ctx: int):
        """ShapeDtypeStructs of a pooled entry at ctx bucket `ctx` — by
        construction the exact output shape of `slice_slot_prefix`."""
        grouped, growing = self.kv._grouped, self.kv._growing
        _, cspec = self._aot_specs()
        return jax.eval_shape(
            lambda c: slice_slot_prefix(c, jnp.int32(0), ctx, grouped,
                                        growing), cspec)

    def _get_shared(self, pad_to: int, ctx: int):
        """Fetch (or AOT-compile) the shared-prefix program for one (delta
        token bucket, pooled ctx bucket). Compile time goes to
        `self.compile_s`, never into measured dt."""
        key = self._prefill_cache_key("shared", pad_to, ctx)
        fn = _AOT_PREFILL_CACHE.get(key)
        if fn is None:
            t0 = time.perf_counter()
            pspec, cspec = self._aot_specs()
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            fn = self._build_shared(
                ctx, program_name("shared", pad_to, ctx)).lower(
                pspec, cspec, self._pool_specs(ctx),
                jax.ShapeDtypeStruct((pad_to,), jnp.int32),
                scalar, scalar, scalar).compile()
            self.compile_s += time.perf_counter() - t0
            _AOT_PREFILL_CACHE[key] = fn
        return fn

    def warmup_prefill(self, lengths=None, ctx_limits=None) -> float:
        """Pre-compile the AOT prefill programs so a cold replica never
        charges a compile to its first conversations' TTFT. `lengths`
        defaults to every PREFILL_BUCKET reachable under max_ctx; turn-1
        programs compile per length, append programs per (length, ctx)
        pair with `ctx_limits` defaulting to every power-of-two ctx bucket
        a prefix could occupy. Returns seconds spent compiling (also
        accumulated in `self.compile_s`). No-op for families the jitted
        path does not cover."""
        if not self._prefill_jittable:
            return 0.0
        if lengths is None:
            lengths = [b for b in PREFILL_BUCKETS if b <= self.kv.max_ctx]
        if ctx_limits is None:
            ctx_limits = []
            b = CTX_BUCKET_MIN
            while b < self.kv.max_ctx:
                ctx_limits.append(b)
                b *= 2
            ctx_limits.append(self.kv.max_ctx)
        before = self.compile_s
        n_front = 0
        if self.cfg.frontend != "none" and self.cfg.frontend_len:
            n_front = self.cfg.frontend_len
        for L in dict.fromkeys(bucket_len(int(x)) for x in lengths):
            self._get_prefill(L, n_front)
            for C in dict.fromkeys(ctx_bucket(int(c), self.kv.max_ctx)
                                   for c in ctx_limits):
                # skip (L, C) pairs no live slot could ever reach: the
                # smallest prefix length in ctx bucket C plus the append
                # must still fit the slot
                min_prev = 0 if C <= CTX_BUCKET_MIN else C // 2 + 1
                if min_prev + L <= self.kv.max_ctx:
                    self._get_append(L, C)
                    if self.prefix_pool is not None:
                        self._get_shared(L, C)
        return self.compile_s - before

    @traced("engine.prefill")
    def prefill_conversation(self, slot: int, tokens: np.ndarray,
                             frontend_embeds=None, prefix_len: int = 0
                             ) -> Tuple[np.ndarray, float]:
        """Turn-1 prefill into `slot`. Returns (next_token, measured_s);
        AOT compile time (cold bucket) is charged to `self.compile_s`,
        never to the returned dt.

        `prefix_len` > 0 declares tokens[:prefix_len] a SHARED PREAMBLE and
        ALWAYS splits the prefill at that boundary — turn-1 class on the
        preamble, append class on the delta — whether or not a pool is
        configured or holds the rows. The split, not the pool, fixes the
        math: both the pool-hit and the recompute path run the same
        masked forward over the same prefix-read downstream, so per-turn
        token streams are byte-identical pool-on vs pool-off. The pool
        only changes WHERE the preamble rows come from: a hit folds the
        pooled rows into the slot (one fused dispatch, zero preamble
        FLOPs); a miss recomputes them and then materializes zero-masked
        copies into the pool for the next conversation."""
        true_len = len(tokens)
        if prefix_len:
            if not 0 < prefix_len < true_len:
                raise ValueError(
                    f"prefill_conversation: prefix_len {prefix_len} must be "
                    f"in (0, {true_len}) — the turn needs a non-empty delta "
                    f"after the shared preamble")
            if frontend_embeds is not None:
                raise ValueError(
                    "prefill_conversation: shared-prefix split does not "
                    "compose with frontend embeds")
            return self._prefill_split(slot, np.asarray(tokens, np.int32),
                                       int(prefix_len))
        n_front = 0
        if self.cfg.frontend != "none" and frontend_embeds is not None:
            n_front = frontend_embeds.shape[1]
        self._check_prefill_room(slot, n_front + true_len)
        if not self._use_jit_prefill():
            return self._prefill_reference(slot, tokens, frontend_embeds,
                                           n_front)
        pad_to = self._prefill_pad(true_len, self.kv.max_ctx - n_front)
        fn = self._get_prefill(pad_to, n_front)  # compile OFF the clock
        toks = np.zeros(pad_to, np.int32)
        toks[:true_len] = tokens
        t0 = time.perf_counter()
        caches, tok = fn(self.params, self.kv.caches, jnp.asarray(toks),
                         np.int32(slot), np.int32(true_len), frontend_embeds)
        tok = jax.block_until_ready(tok)
        self.kv.caches = caches  # donated: old buffers are dead
        self.kv.lengths[slot] = n_front + true_len
        dt = time.perf_counter() - t0
        self.compute_s += dt
        self.prefill_s += dt
        self.n_prefill_tokens += true_len
        self.tracer.timed(t0, dt, tokens=true_len, bucket=pad_to,
                          program=program_name("prefill", pad_to,
                                               n_front=n_front))
        return np.int32(tok), dt

    def _prefill_split(self, slot: int, tokens: np.ndarray, prefix_len: int
                       ) -> Tuple[np.ndarray, float]:
        """Shared-preamble turn-1 prefill: the always-split path behind
        `prefill_conversation(prefix_len=...)`. Pool hit -> fused
        shared-prefix program (or the host-side fold + eager append in
        reference mode); miss or no pool -> turn-1 class on the preamble,
        pool populate (when enabled), append class on the delta."""
        self._check_prefill_room(slot, len(tokens))
        prefix = tokens[:prefix_len]
        delta = tokens[prefix_len:]
        pool = self.prefix_pool
        key = prefix_hash(prefix) if pool is not None else None
        if pool is not None and pool.contains(key):
            return self._prefill_from_pool(slot, key, delta, prefix_len)
        # Miss (or no pool): recompute the preamble through the normal
        # turn-1 class, then serve the delta through the append class —
        # the exact programs a pool hit replays, so the streams match.
        tok_p, dt = self.prefill_conversation(slot, prefix)
        del tok_p  # the preamble's sampled token is never emitted
        if pool is not None:
            t0 = time.perf_counter()
            ctx = ctx_bucket(prefix_len, self.kv.max_ctx)
            rows = self._materialize_prefix(slot, prefix_len, ctx)
            pool.put(key, rows, prefix_len, ctx)
            export_dt = time.perf_counter() - t0
            self.compute_s += export_dt
            self.prefill_s += export_dt
            dt += export_dt
        tok, dt_a = self.append_prefill(slot, delta)
        return tok, dt + dt_a

    def _materialize_prefix(self, slot: int, length: int, ctx: int):
        """Copy a slot's first `length` cache rows out at ctx bucket `ctx`,
        zero-masked beyond `length` — the immutable pooled representation.
        Must run BEFORE the delta append touches the slot (fixed-state
        leaves would otherwise reflect the full context) and before any
        donated program kills the buffers the slice reads."""
        grouped, growing = self.kv._grouped, self.kv._growing
        rows = slice_slot_prefix(self.kv.caches, jnp.int32(slot), ctx,
                                 grouped, growing)

        def mask(leaf, g, gr):
            if not gr:
                return leaf
            if g:  # (G, 1, ctx, ...)
                pos = jnp.arange(leaf.shape[2]).reshape(
                    (1, 1, -1) + (1,) * (leaf.ndim - 3))
            else:  # (1, ctx, ...)
                pos = jnp.arange(leaf.shape[1]).reshape(
                    (1, -1) + (1,) * (leaf.ndim - 2))
            return jnp.where(pos < length, leaf, jnp.zeros_like(leaf))

        rows = jax.tree_util.tree_map(mask, rows, grouped, growing)
        return jax.block_until_ready(rows)

    def _prefill_from_pool(self, slot: int, key: str, delta: np.ndarray,
                           prefix_len: int) -> Tuple[np.ndarray, float]:
        """Pool-hit turn-1: fold the pooled preamble rows into the slot and
        run the delta forward against them — zero preamble FLOPs. The entry
        is pinned across the read so eviction can never rip the rows out
        from under the dispatch; `get` records the observed hit the
        eviction rule orders on."""
        pool = self.prefix_pool
        e = pool.get(key)
        pool.pin(key)
        try:
            true_len = len(delta)
            if not self._use_jit_prefill():
                # reference mode: host-side fold of the pooled rows, then
                # the eager append oracle over them
                t0 = time.perf_counter()
                self.kv.caches = fold_prefill(
                    self.kv.caches, e.caches, slot, 0,
                    self.kv._grouped, self.kv._growing)
                self.kv.lengths[slot] = prefix_len
                fold_dt = time.perf_counter() - t0
                self.compute_s += fold_dt
                self.prefill_s += fold_dt
                tok, dt = self._append_reference(slot, delta)
                self.n_pooled_prefix_tokens += prefix_len
                return tok, fold_dt + dt
            pad_to = self._prefill_pad(true_len,
                                       self.kv.max_ctx - prefix_len)
            fn = self._get_shared(pad_to, e.ctx)  # compile OFF the clock
            toks = np.zeros(pad_to, np.int32)
            toks[:true_len] = delta
            t0 = time.perf_counter()
            caches, tok = fn(self.params, self.kv.caches, e.caches,
                             jnp.asarray(toks), np.int32(slot),
                             np.int32(true_len), np.int32(prefix_len))
            tok = jax.block_until_ready(tok)
            self.kv.caches = caches  # donated: old buffers are dead
            self.kv.lengths[slot] = prefix_len + true_len
            dt = time.perf_counter() - t0
            self.compute_s += dt
            self.prefill_s += dt
            self.n_prefill_tokens += true_len
            self.n_pooled_prefix_tokens += prefix_len
            self.tracer.timed(t0, dt, tokens=true_len, bucket=pad_to,
                              program=program_name("shared", pad_to, e.ctx))
            return np.int32(tok), dt
        finally:
            pool.unpin(key)

    def _prefill_reference(self, slot: int, tokens: np.ndarray,
                           frontend_embeds, n_front: int
                           ) -> Tuple[np.ndarray, float]:
        """REFERENCE PATH (pre-AOT): eager per-op forward + host-side
        `write_prefill` copy. The parity oracle and benchmark baseline."""
        t0 = time.perf_counter()
        true_len = len(tokens)
        pad_to = true_len if self.exact_prefill else self._prefill_pad(
            true_len, self.kv.max_ctx - n_front)
        toks = np.zeros(pad_to, np.int32)
        toks[:true_len] = tokens
        logits, caches = self.model.prefill(
            self.params, jnp.asarray(toks)[None],
            frontend_embeds=frontend_embeds,
            logits_at=true_len - 1 if pad_to != true_len else None)
        logits = jax.block_until_ready(logits)
        self.kv.write_prefill(slot, caches, n_front + true_len)
        dt = time.perf_counter() - t0
        self.compute_s += dt
        self.prefill_s += dt
        self.n_prefill_tokens += true_len
        self.tracer.timed(t0, dt, tokens=true_len, bucket=pad_to,
                          program="prefill_reference")
        return self.sample(logits)[0], dt

    @traced("engine.append")
    def append_prefill(self, slot: int, tokens: np.ndarray
                       ) -> Tuple[np.ndarray, float]:
        """Turn-2+ prefill against the slot's cached prefix (local, prefix
        cache hit — the ConServe fast path). Returns (next_token,
        measured_s); AOT compile time is charged to `self.compile_s`."""
        true_len = len(tokens)
        self._check_prefill_room(slot, true_len)
        if not self._use_jit_prefill():
            return self._append_reference(slot, tokens)
        prev = int(self.kv.lengths[slot])
        pad_to = self._prefill_pad(true_len, self.kv.max_ctx - prev)
        ctx = ctx_bucket(max(prev, 1), self.kv.max_ctx)
        fn = self._get_append(pad_to, ctx)  # compile OFF the clock
        toks = np.zeros(pad_to, np.int32)
        toks[:true_len] = tokens
        t0 = time.perf_counter()
        caches, tok = fn(self.params, self.kv.caches, jnp.asarray(toks),
                         np.int32(slot), np.int32(true_len), np.int32(prev))
        tok = jax.block_until_ready(tok)
        self.kv.caches = caches  # donated: old buffers are dead
        self.kv.lengths[slot] = prev + true_len
        dt = time.perf_counter() - t0
        self.compute_s += dt
        self.prefill_s += dt
        self.n_prefill_tokens += true_len
        self.tracer.timed(t0, dt, tokens=true_len, bucket=pad_to,
                          program=program_name("append", pad_to, ctx))
        return np.int32(tok), dt

    def _append_reference(self, slot: int, tokens: np.ndarray
                          ) -> Tuple[np.ndarray, float]:
        """REFERENCE PATH (pre-AOT): eager forward over the full-buffer
        prefix view (`export_slot_full` host-side copy) + host-side
        `write_prefill`. The parity oracle and benchmark baseline."""
        t0 = time.perf_counter()
        true_len = len(tokens)
        prev = int(self.kv.lengths[slot])
        pad_to = true_len if self.exact_prefill else self._prefill_pad(
            true_len, self.kv.max_ctx - prev)
        toks = np.zeros(pad_to, np.int32)
        toks[:true_len] = tokens
        prefix = self.kv.export_slot_full(slot)
        lens = jnp.asarray([prev], jnp.int32)
        logits, caches = self.model.prefill(
            self.params, jnp.asarray(toks)[None], caches=prefix,
            start_pos=prev, kv_lens=lens, prefix_start=0,
            logits_at=true_len - 1 if pad_to != true_len else None)
        logits = jax.block_until_ready(logits)
        self.kv.write_prefill(slot, caches, prev + true_len)
        dt = time.perf_counter() - t0
        self.compute_s += dt
        self.prefill_s += dt
        self.n_prefill_tokens += true_len
        self.tracer.timed(t0, dt, tokens=true_len, bucket=pad_to,
                          program="append_reference")
        return self.sample(logits)[0], dt

    # ----- decode -----------------------------------------------------------------
    def _step(self, params, tokens, caches, lens, live,
              ctx_limit: Optional[int]):
        """One decode step of every slot with its cache writes: the
        arithmetic both decode modes share. Slot s decodes at position
        lens[s]; a slot that is not `live` keeps its cache row, state and
        length byte for byte. `ctx_limit` (static, or None for the whole
        buffer) bounds the cache read. Returns (logits, caches)."""
        if self._in_place:
            return self.model.decode_lanes(params, tokens, caches, lens, live,
                                           ctx_limit=ctx_limit)
        logits, updates = self.model.decode_step(
            params, tokens, caches, lens, kv_lens=lens, ctx_limit=ctx_limit,
            attention_impl=self.attention_impl)
        with jax.named_scope("kv_fold"):
            return logits, fold_decode_step(caches, updates, lens, live,
                                            self.kv._grouped,
                                            self.kv._growing)

    def _kv_read(self, n_steps: int, lens: np.ndarray, rem: np.ndarray,
                 ctx: Optional[int]) -> int:
        """KV positions per layer a decode call reads, for lanes at lengths
        `lens` taking `rem` steps each: each live lane-step's length rounded
        up to the kernel's block (at least one block) where the read is
        length-bounded; its first `ctx` positions through the XLA lane loop;
        or every slot's whole buffer at every step where the layer scan
        slices whole slabs (Pallas, encoder-decoder)."""
        if self._lane_kernel:
            bk = self._lane_block
            step = np.arange(n_steps)
            blocks = np.maximum(-(-(lens[:, None] + step) // bk), 1)
            return int(bk * blocks[step < rem[:, None]].sum())
        if self._in_place:
            return int(np.sum(rem)) * ctx
        return n_steps * self.kv.n_slots * self.kv.max_ctx

    def _build_fused(self, n_steps: int, ctx_limit: Optional[int]):
        """Fused decode program: scan over `n_steps` iterations with
        on-device greedy sampling fed back as the next token and the
        per-slot cache write fused into the step (`_step`). The cache pytree is
        DONATED — XLA aliases the input buffers into the outputs, so the
        decode tail appends in place instead of copying every leaf per
        token. The scan is ragged: `remaining` is a per-slot step count and
        slot s is a masked no-op from step remaining[s] on (its KV stops
        folding, its length stops advancing, its fed-back token freezes),
        so one compiled bucket serves any mix of per-slot chunk lengths up
        to n_steps."""
        vocab = self.cfg.vocab_size

        def run(params, caches, tokens, lens, emit, remaining):
            def body(carry, i):
                caches, lens, tokens = carry
                live = emit & (i < remaining)
                logits, caches = self._step(params, tokens, caches, lens,
                                            live, ctx_limit)
                with jax.named_scope("sample"):
                    sampled = jnp.argmax(logits[:, :vocab], axis=-1).astype(
                        jnp.int32)
                lens = lens + live.astype(lens.dtype)
                tokens = jnp.where(live, sampled, tokens)
                return (caches, lens, tokens), sampled

            (caches, lens, tokens), seq = jax.lax.scan(
                body, (caches, lens, tokens), jnp.arange(n_steps))
            return caches, seq

        return jax.jit(
            _named(run, program_name("decode_fused", n_steps, ctx_limit)),
            donate_argnums=(1,))

    def _get_fused(self, n_steps: int, ctx_limit: Optional[int]):
        """Fetch (or AOT-compile) the fused program for one (chunk, ctx)
        bucket, or for the chunk alone (ctx None) where the read is
        length-bounded. Compile time goes to `self.compile_s`, NOT into any
        measured decode dt — first bucket hits no longer pollute the
        server's logical clock or the observed TBT EMA."""
        if self._lane_kernel:
            ctx_limit = None
        key = (n_steps, ctx_limit)
        fn = self._fused.get(key)
        if fn is None:
            t0 = time.perf_counter()
            spec = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
                jnp.shape(x), x.dtype)
            vec = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
                (self.kv.n_slots,), dt)
            fn = self._build_fused(n_steps, ctx_limit).lower(
                jax.tree_util.tree_map(spec, self.params),
                jax.tree_util.tree_map(spec, self.kv.caches),
                vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
                vec(jnp.int32)).compile()
            self.compile_s += time.perf_counter() - t0
            self._fused[key] = fn
        return fn

    def warmup_decode(self, chunks=None, ctx_limits=None) -> float:
        """Pre-compile fused decode programs so serving never hits a cold
        (chunk, ctx) bucket. Defaults cover every bucket reachable on this
        replica: all DECODE_CHUNKS × all power-of-two ctx buckets up to
        max_ctx; where the read is length-bounded, the chunks alone
        (`_get_fused` drops the ctx). Returns the seconds spent compiling
        (also accumulated in `self.compile_s`)."""
        if ctx_limits is None:
            ctx_limits = []
            b = CTX_BUCKET_MIN
            while b < self.kv.max_ctx:
                ctx_limits.append(b)
                b *= 2
            ctx_limits.append(self.kv.max_ctx)
        before = self.compile_s
        for c in (chunks if chunks is not None else DECODE_CHUNKS):
            for cl in dict.fromkeys(ctx_limits):
                self._get_fused(decode_chunk_bucket(int(c)), cl)
        return self.compile_s - before

    def compiled_programs(self) -> Dict[Tuple, Any]:
        """Every AOT executable this replica can dispatch, keyed by
        (kind, *bucket): ("decode", chunk, ctx) from its own fused cache,
        ("prefill" | "append" | "shared", ...) from the process-wide prefill
        cache entries matching its signature. For inspecting what was
        compiled (`.as_text()`, `.memory_analysis()`)."""
        progs = {("decode", *k): fn for k, fn in self._fused.items()}
        sig = self._prefill_cache_key("")[:-1]
        n = len(sig)
        progs.update({k[n:]: fn for k, fn in _AOT_PREFILL_CACHE.items()
                      if k[:n] == sig})
        return progs

    def _remaining_vector(self, emit_mask: np.ndarray,
                          remaining) -> np.ndarray:
        """Normalize `remaining` (scalar or per-slot vector) into a
        validated per-slot int32 vector, enforcing the per-slot overflow
        guard (raises naming the offending slot, not the batch max)."""
        if np.ndim(remaining) == 0:
            n = int(max(1, min(int(remaining), DECODE_CHUNKS[-1])))
            rem = np.where(emit_mask, n, 0).astype(np.int32)
        else:
            rem = np.asarray(remaining, np.int32).copy()
            if rem.shape != emit_mask.shape:
                raise ValueError(
                    f"decode_steps: remaining shape {rem.shape} != "
                    f"emit_mask shape {emit_mask.shape}")
            rem[~emit_mask] = 0
            bad = emit_mask & (rem <= 0)
            if bad.any():
                raise ValueError(
                    "decode_steps: emitting slot(s) "
                    f"{np.flatnonzero(bad).tolist()} have non-positive "
                    "remaining")
            big = emit_mask & (rem > DECODE_CHUNKS[-1])
            if big.any():
                # the contract is 'slot s consumes EXACTLY remaining[s]
                # tokens' — silently clamping would desync the caller's
                # bookkeeping from kv.lengths, so refuse instead
                s = int(np.flatnonzero(big)[0])
                raise ValueError(
                    f"decode_steps: slot {s} remaining {int(rem[s])} "
                    f"exceeds the largest compiled chunk "
                    f"{DECODE_CHUNKS[-1]}; chunk the call")
        over = emit_mask & (self.kv.lengths + rem > self.kv.max_ctx)
        if over.any():
            s = int(np.flatnonzero(over)[0])
            # the in-scan scatter would clamp at the last position while
            # host lengths advance past the buffer — refuse loudly here so
            # every caller gets the guarantee, not just EngineServer
            raise RuntimeError(
                f"decode_steps overflow: slot {s} at length "
                f"{int(self.kv.lengths[s])} cannot take {int(rem[s])} more "
                f"tokens (max_ctx={self.kv.max_ctx})")
        return rem

    @traced("engine.decode_chunk")
    def decode_steps(self, next_tokens: np.ndarray, emit_mask: np.ndarray,
                     remaining) -> Tuple[np.ndarray, float]:
        """Run one RAGGED fused decode chunk across ALL slots in ONE
        dispatch (inactive slots compute in lockstep but are masked out).

        `remaining` is either a scalar int — every emitting slot consumes
        exactly that many tokens (clamped into [1, DECODE_CHUNKS[-1]], the
        historic contract) — or a per-slot int vector: slot s consumes
        exactly remaining[s] tokens (each must be in [1, DECODE_CHUNKS[-1]];
        larger values raise rather than silently clamp), then freezes
        mid-scan while longer-running neighbors continue to
        max(remaining). Returns
        (sampled (max(remaining), n_slots) int32 matrix in step order —
        rows >= remaining[s] are dead for slot s — and measured execution
        seconds; AOT compile time is charged to `self.compile_s`, never to
        the returned dt).

        SPLIT-CHUNK CONTRACT (what the server's rotation loop relies on):
        `decode_steps` is callable back-to-back on the same donated cache,
        and slots may JOIN between calls — a slot prefilled (or imported)
        after call k participates in call k+1 exactly as if the whole
        sequence had been one dispatch schedule from the start. This is
        sound by construction, not by convention: each lane's math reads
        only its own slot's cache row and length, a frozen/inactive lane's
        row is select-guarded to byte-identity (`_step`), and
        per-slot lengths advance by exactly the consumed share — so ANY
        partition of a turn's remaining tokens into chunk cuts, interleaved
        with other slots joining or finishing, yields byte-identical
        per-slot tokens and cache state (locked down by the rotation
        hypothesis property in tests/test_scheduler_properties.py)."""
        emit_mask = np.asarray(emit_mask, bool)
        rem = self._remaining_vector(emit_mask, remaining)
        n_max = int(rem.max()) if emit_mask.any() else 1
        n_max = max(1, n_max)
        n_steps = decode_chunk_bucket(n_max)
        live_max = int(self.kv.lengths[emit_mask].max()) if emit_mask.any() \
            else 0
        ctx_limit = None if self._lane_kernel else ctx_bucket(
            live_max + n_steps, self.kv.max_ctx)
        fn = self._get_fused(n_steps, ctx_limit)
        lens0 = self.kv.lengths[emit_mask].copy()
        t0 = time.perf_counter()
        caches, seq = fn(self.params, self.kv.caches,
                         jnp.asarray(next_tokens, jnp.int32),
                         jnp.asarray(self.kv.lengths),
                         jnp.asarray(emit_mask), jnp.asarray(rem))
        with self.tracer.span("engine.device_wait"):
            seq = jax.block_until_ready(seq)
        seq = np.asarray(seq)[:n_max]
        self.kv.caches = caches  # donated: old buffers are dead
        self.kv.lengths += np.where(emit_mask, rem, 0).astype(np.int32)
        dt = time.perf_counter() - t0
        self.compute_s += dt
        self.decode_s += dt
        live = int(rem[emit_mask].sum())
        self.n_decode_tokens += live
        self.tracer.timed(t0, dt, node=self.replica_id,
                          program=program_name("decode_fused", n_steps,
                                               ctx_limit),
                          n_steps=n_steps, ctx=ctx_limit,
                          lanes=int(emit_mask.sum()), live=live,
                          kv_read=self._kv_read(n_steps, lens0,
                                                rem[emit_mask], ctx_limit))
        return seq, dt

    def decode_step_all(self, next_tokens: np.ndarray,
                        emit_mask: np.ndarray) -> Tuple[np.ndarray, float]:
        """One continuous-batching iteration across ALL slots via the fused
        in-place path. Returns (sampled (n_slots,), measured_s)."""
        seq, dt = self.decode_steps(next_tokens, emit_mask, 1)
        return seq[0], dt

    @traced("engine.decode_chunk")
    def decode_step_all_reference(self, next_tokens: np.ndarray,
                                  emit_mask: np.ndarray
                                  ) -> Tuple[np.ndarray, float]:
        """REFERENCE PATH (pre-fusion): one jitted dispatch + host sync +
        host-side argmax per token over the fused scan's own step
        (`_step`, the emitting slots live), reading the whole buffer and
        writing a copy of the cache. Kept as the parity oracle and
        benchmark baseline."""
        t0 = time.perf_counter()
        emit_mask = np.asarray(emit_mask, bool)
        lens0 = self.kv.lengths[emit_mask].copy()
        logits, caches = self._decode(
            self.params, jnp.asarray(next_tokens), self.kv.caches,
            self.kv.kv_lens(), jnp.asarray(emit_mask))
        with self.tracer.span("engine.device_wait"):
            logits = jax.block_until_ready(logits)
        self.kv.caches = caches
        self.kv.lengths[emit_mask] += 1
        dt = time.perf_counter() - t0
        self.compute_s += dt
        self.decode_s += dt
        lanes = int(emit_mask.sum())
        self.n_decode_tokens += lanes
        ctx = None if self._lane_kernel else self.kv.max_ctx
        self.tracer.timed(t0, dt, node=self.replica_id,
                          program="decode_reference", n_steps=1,
                          ctx=ctx, lanes=lanes, live=lanes,
                          kv_read=self._kv_read(1, lens0,
                                                np.ones(lanes, np.int32),
                                                ctx))
        return self.sample(logits), dt
