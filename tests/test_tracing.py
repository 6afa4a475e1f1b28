"""The engine's own spans and events (repro.core.tracing): off records and
annotates nothing; on, spans nest, every served turn has one `engine.turn`
whose work carries its request, the turn span brackets what the event bus
shows, compiles name the span they fell in, and every AOT program and
model scope has a stable name in the compiled text."""
import re
import time
from collections import Counter, defaultdict

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import make_scheduler
from repro.core.conversation import Conversation, Turn
from repro.core.events import EV_SESSION, EV_TOKENS
from repro.core.runtime import TOOL_WAIT
from repro.core.tracing import (ADMISSION, COMPILE, NO_SPAN, NULL_TRACER,
                                TURN, Tracer)
from repro.cluster.simulator import ClusterSimulator
from repro.engine import EngineServer, ReplicaEngine
from repro.models import build_model

WORK = ("engine.prefill", "engine.append", "engine.kv_export",
        "engine.kv_import")


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen3-0.6b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _convs(n=3):
    """n conversations arriving together at t=0: a turn-1 prefill each, one
    KV move, then a tool turn with an append."""
    return [Conversation(cid=c, arrival_s=0.0, turns=[
        Turn(append_tokens=20 + 7 * c, output_tokens=3 + c, tool_time_s=0.0),
        Turn(append_tokens=9 + c, output_tokens=2, tool_time_s=0.0)])
        for c in range(n)]


def _server(cfg, params, tracing, n_slots=4, **kw):
    reps = [ReplicaEngine(cfg, params, n_slots=n_slots, max_ctx=256,
                          replica_id=0, role="prefill"),
            ReplicaEngine(cfg, params, n_slots=n_slots, max_ctx=256,
                          replica_id=1)]
    return EngineServer(make_scheduler("conserve"), reps, tracing=tracing,
                        **kw)


class _CountingAnnotation:
    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _CountingAnnotation.entered += 1
        return self

    def __exit__(self, *exc):
        return False


def test_tracing_off_records_and_annotates_nothing(qwen, monkeypatch):
    cfg, params = qwen
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.entered = 0
    srv = _server(cfg, params, tracing=False)
    assert srv.serve(_convs())
    tr = srv.tracer
    assert not tr.enabled and len(tr.records) == 0 and not tr._open
    assert tr.span("engine.x") is NO_SPAN
    assert tr.resume(0, 0) is NO_SPAN
    assert all(r.tracer is tr and r.kv.tracer is tr
               for r in srv.replicas.values())
    assert _CountingAnnotation.entered == 0
    # the simulator keeps the shared tracer, which refuses to turn on
    assert ClusterSimulator.tracer is NULL_TRACER
    with pytest.raises(RuntimeError):
        NULL_TRACER.enable()

    on = _server(cfg, params, tracing=True)
    on.serve(_convs())
    assert _CountingAnnotation.entered > 0
    assert len(on.tracer.records) > 0


def test_spans_nest_and_turns_own_their_work(qwen):
    cfg, params = qwen
    srv = _server(cfg, params, tracing=True)
    recs = srv.serve(_convs())
    records = list(srv.tracer.records)
    by_id = {r.id: r for r in records}
    names = Counter(r.name for r in records)
    for name in ("engine.prefill", "engine.append", "engine.kv_export",
                 "engine.kv_import", "engine.decode_chunk",
                 "engine.device_wait", TURN):
        assert names[name] > 0, (name, names)

    for r in records:
        if r.parent is None:
            continue
        p = by_id[r.parent]
        if r.name == COMPILE:
            assert r.attrs["span"] == p.name
            continue
        # a child starts and ends inside its parent
        assert p.t0 <= r.t0 <= r.t1 <= p.t1, (r, p)
        assert (r.cid, r.turn) == (p.cid, p.turn), (r, p)
    for r in records:
        if r.name == "engine.device_wait":
            assert by_id[r.parent].name == "engine.decode_chunk"
        if r.name == "engine.decode_chunk":
            assert r.attrs["program"].startswith("decode_fused_n")
            assert r.attrs["live"] <= r.attrs["lanes"] * r.attrs["n_steps"]

    turns = defaultdict(list)
    for r in records:
        if r.name == TURN:
            turns[(r.cid, r.turn)].append(r)
    served = {(c.cid, t.turn_idx) for c in recs for t in c.turns}
    assert set(turns) == served
    kids = defaultdict(list)
    for r in records:
        kids[r.parent].append(r)
    for (cid, idx), (turn,) in turns.items():
        work = [k for k in kids[turn.id] if k.name in WORK]
        assert all((k.cid, k.turn) == (cid, idx) for k in work)
        got = sorted(k.name for k in work)
        if idx == 0:  # disaggregated: prefill there, one move here
            assert got == ["engine.kv_export", "engine.kv_import",
                           "engine.prefill"], got
            move = [k for k in work if k.name != "engine.prefill"]
            assert all(k.attrs["bytes"] > 0 and k.attrs["length"] > 0
                       for k in move)
        else:
            assert got == ["engine.append"], got


def test_turn_span_brackets_the_bus(qwen):
    cfg, params = qwen
    srv = _server(cfg, params, tracing=True)
    first, runnable = {}, {}
    pc = time.perf_counter

    def on_event(ev):
        now = pc()
        if ev.kind == EV_TOKENS:
            first.setdefault((ev.cid, ev.turn_idx), now)
        elif ev.data.get("prev") == TOOL_WAIT:
            runnable[(ev.cid, ev.turn_idx)] = now

    srv.bus.subscribe(on_event, kinds=[EV_TOKENS, EV_SESSION])
    convs = _convs()
    srv.submit(convs)
    for c in convs:
        runnable[(c.cid, 0)] = pc()
    srv.run()
    turns = {(r.cid, r.turn): r for r in srv.tracer.records
             if r.name == TURN}
    assert set(turns) == set(first) == set(runnable)
    for key, t in turns.items():
        assert t.t0 <= runnable[key] < first[key] <= t.t1, key


def test_parked_work_has_an_admission_span(qwen):
    cfg, params = qwen
    srv = _server(cfg, params, tracing=True, n_slots=1)
    srv.serve(_convs(3))
    records = list(srv.tracer.records)
    turn_of = {(r.cid, r.turn): r.id for r in records if r.name == TURN}
    adm = [r for r in records if r.name == ADMISSION]
    assert adm and srv.n_deferred_admissions >= len(adm)
    for a in adm:
        assert a.parent == turn_of[(a.cid, a.turn)]
        assert a.t1 >= a.t0


def test_compile_event_names_its_span(qwen):
    cfg, params = qwen
    eng = ReplicaEngine(cfg, params, n_slots=2, max_ctx=128)
    tr = eng.tracer = eng.kv.tracer = Tracer(enabled=True)
    slot = eng.kv.acquire()
    tok, _ = eng.prefill_conversation(slot, np.arange(5, 45, dtype=np.int32))
    emit = np.zeros(2, bool)
    emit[slot] = True
    nt = np.zeros(2, np.int32)
    nt[slot] = int(tok)
    eng.decode_steps(nt, emit, 3)  # a fresh (4, 64) bucket on this replica
    records = list(tr.records)
    chunk = next(r for r in records if r.name == "engine.decode_chunk")
    compiles = [r for r in records if r.name == COMPILE
                and r.parent == chunk.id]
    assert compiles, records
    assert all(c.attrs["span"] == "engine.decode_chunk" for c in compiles)
    assert any("decode_fused_n4_c64" in c.attrs["program"] for c in compiles)
    assert chunk.attrs["program"] == "decode_fused_n4_c64"


def test_programs_have_distinct_names_and_model_scopes(qwen):
    cfg, params = qwen
    eng = ReplicaEngine(cfg, params, n_slots=2, max_ctx=128)
    eng.warmup_prefill(lengths=[32], ctx_limits=[64])
    eng.warmup_decode(chunks=[2], ctx_limits=[64])
    texts = {k: fn.as_text() for k, fn in eng.compiled_programs().items()}
    modules = {k: re.search(r"^HloModule (\S+?),", t, re.M).group(1)
               for k, t in texts.items()}
    assert modules[("decode", 2, 64)] == "jit_decode_fused_n2_c64"
    assert modules[("prefill", 32, 0)] == "jit_prefill_b32"
    assert modules[("append", 32, 64)] == "jit_append_b32_c64"
    assert len(set(modules.values())) == len(modules)
    scopes = set()
    for name in re.findall(r'op_name="([^"]*)"',
                           texts[("decode", 2, 64)]):
        scopes.update(name.split("/"))
    assert {"embed", "layers", "attn", "qkv", "core", "out", "mlp", "norm",
            "logits", "sample", "kv_fold"} <= scopes, scopes
    # the layer scan's own per-layer slicing sits under layers, outside
    # every child scope
    assert re.search(r'op_name="[^"]*/layers/while/body/dynamic_slice"',
                     texts[("decode", 2, 64)])
