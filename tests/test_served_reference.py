"""The served path against the benchmark's plain reference, on the CPU.

A seeded short agentic trace is served through `EngineServer` (ConServe, a
prefiller and a decoder, fused decode) at a configuration's float32
reduction, and at every served position the served token's logit gap is
read against `bench/references/dense_gqa.py`: the same comparison that
decides a benchmark cell's `correct` on the chip, at a size the CPU runs in
seconds. The reference is loaded by path, draws its own weights from the
seed, and takes the configuration's norm gains as the benchmark sets them."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import make_scheduler
from repro.engine import EngineServer, ReplicaEngine
from repro.models import build_model
from repro.traces import TraceConfig, generate_trace

BENCH = Path(__file__).resolve().parents[1] / "bench"
SEED = 2147483011 % (2 ** 31 - 1)
MAX_CTX = 256

# Both sides compute in float32 and differ only in the order of their sums
# (the program's cache, chunking and fused decode against one causal
# forward), so the served token is the reference's best wherever the two
# agree on the argmax, and a gap can only come from a near-tie that a
# float32 rounding (about 1e-6 of logits of order 1) tips: the mean stays
# far below 1e-4. A wrong token, a stale KV row or a misplaced position
# lies a sizeable part of the logits' spread (0.1 and more) below the best.
MEAN_GAP = 1e-4
# the largest single gap: a tipped near-tie is at most the logits' float32
# rounding through a few layers, two orders of magnitude under 1e-3
MAX_GAP = 1e-3


def _reference():
    path = BENCH / "references" / "dense_gqa.py"
    spec = importlib.util.spec_from_file_location("dense_gqa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model_section(arch: str, cfg) -> dict:
    """The benchmark configuration's `model` section at the float32
    reduction's sizes, as `bench/run.py` makes it for a reduced run."""
    conf = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    file = next(c["file"] for c in conf["configs"] if c["name"] == arch)
    m = dict(json.loads((BENCH.parent / file).read_text())["model"])
    m.update(n_layers=cfg.n_layers, d_model=cfg.d_model,
             n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
             head_dim=cfg.head_dim, d_ff=cfg.d_ff,
             vocab_size=cfg.vocab_size, dtype=cfg.dtype)
    return m


def _set_gains(params, ref, m: dict, gain: float):
    """Every norm gain at `gain`, as `bench/run.py`'s `set_gains` sets
    them, after checking they are the gains the reference draws."""
    seen = []

    def one(path, x):
        key = jax.tree_util.keystr(path)
        if key.rsplit("'", 2)[-2].endswith("scale"):
            seen.append(key)
            return jnp.full(x.shape, gain, x.dtype)
        return x
    params = jax.tree_util.tree_map_with_path(one, params)
    assert sorted(seen) == sorted(p for p, _, kind in ref.leaves(m)
                                  if kind == "gain")
    return params


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "olmo-1b"])
def test_served_tokens_match_plain_reference(arch):
    ref = _reference()
    cfg = get_reduced(arch)
    m = _model_section(arch, cfg)
    params = _set_gains(build_model(cfg).init(jax.random.PRNGKey(SEED)),
                        ref, m, 0.0)
    tc = TraceConfig(first_input_median=60, first_input_sigma=0.4,
                     first_input_max=120, append_median=16, append_sigma=0.5,
                     append_max=40, output_median=8, output_sigma=0.6,
                     output_max=16, mean_turns=2.5, max_turns=4,
                     tool_mean_s=0.02, seed=11)
    trace = generate_trace(5, 3.0, cfg=tc)
    reps = [ReplicaEngine(cfg, params, n_slots=4, max_ctx=MAX_CTX,
                          replica_id=0, role="prefill"),
            ReplicaEngine(cfg, params, n_slots=4, max_ctx=MAX_CTX,
                          replica_id=1)]
    srv = EngineServer(make_scheduler("conserve"), reps, seed=SEED,
                       decode_mode="fused", record_tokens=True)
    recs = srv.serve(trace)
    assert sorted(r.cid for r in recs) == [c.cid for c in trace]
    assert all(r.n_kv_transfers == 1 for r in recs)

    w = ref.draw_weights(m, SEED, 0.0)
    gaps = []
    for conv in trace:
        toks, served = [], []
        for i, turn in enumerate(conv.turns):
            stream = srv.sampled_tokens[(conv.cid, i)]
            assert len(stream) == turn.output_tokens + 1
            toks += list(ref.prompt_tokens(SEED, conv.cid, i,
                                           turn.append_tokens, m["vocab_size"]))
            served += [-1] * turn.append_tokens
            served[-1] = stream[0]
            for a, b in zip(stream[:-1], stream[1:]):
                toks.append(a)
                served.append(b)
        assert len(toks) <= MAX_CTX
        gp, _ = ref.gaps(w, m, np.asarray(toks, np.int32),
                         np.asarray(served, np.int32), MAX_CTX)
        gaps.append(gp)
    gaps = np.concatenate(gaps)
    assert len(gaps) == sum(t.output_tokens + 1 for c in trace
                            for t in c.turns)
    assert gaps.mean() < MEAN_GAP, (gaps.mean(), gaps.max())
    assert gaps.max() < MAX_GAP, (gaps.mean(), gaps.max())
