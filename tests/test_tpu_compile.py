"""Compile the served path's kernels and programs for a TPU v5e that is
described, not attached. The TPU compiler is installed with jaxlib, so the
alignment, fast-memory and fit rules the chip enforces are checked here
with no chip: a kernel refused by the chip's compiler fails this file, not
a chip run.

The topology is described inside a module fixture (never while a module is
imported): only one process at a time may load the TPU library, and every
test worker imports every test file. `jax.default_backend()` still reports
the CPU here, so the kernels are called directly with `interpret=False`,
and the engine's Pallas programs are traced with `ops._interpret` steered
to the native path."""
from __future__ import annotations

import json
import os
import re
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.engine.replica import ReplicaEngine  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.decode_attention import flash_decode_attention  # noqa: E402
from repro.models import attention  # noqa: E402
from repro.kernels.prefill_attention import flash_prefill_attention  # noqa: E402
from repro.models.model import Model  # noqa: E402

V5E_HBM_BYTES = 16 * 2**30
# temporaries of the 8-slot, 2048-position, ctx-1024 decode program below
# when its layer scan sliced each layer's whole KV slab for every slot
WHOLE_SLAB_DECODE_TEMP_BYTES = 178_173_440


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_for_v5e(one_chip):
    B, H, Hkv, D, L = 8, 16, 8, 128, 2048
    q = jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, L, Hkv, D), jnp.bfloat16,
                              sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda q, k, v, n: flash_decode_attention(
        q, k, v, n, interpret=False))
    compiled = fn.lower(q, kv, kv, lens).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("S", [512, 4096])
def test_flash_prefill_compiles_for_v5e(one_chip, S):
    x = jax.ShapeDtypeStruct((1, 16, S, 128), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_prefill_attention(
        q, k, v, interpret=False))
    compiled = fn.lower(x, x, x).compile()
    assert _has_kernel(compiled)


@pytest.fixture
def engine(monkeypatch):
    """A full-width replica of `arch` holding shapes, not arrays: params
    are the model skeleton and the slot cache its cache skeleton, so the
    engine's own program builders trace at published widths without
    allocating the weights and the KV buffers."""
    def factory(arch: str, attention_impl: str, n_slots: int, max_ctx: int):
        if attention_impl == "pallas":
            monkeypatch.setattr(ops, "_interpret", lambda: False)
        monkeypatch.setattr(Model, "init_cache",
                            lambda self, b, c: self.cache_skeleton(b, c))
        cfg = get_config(arch)
        return ReplicaEngine(cfg, Model(cfg).skeleton(), n_slots=n_slots,
                             max_ctx=max_ctx, attention_impl=attention_impl)
    return factory


def _check_fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_qwen3_decode_step_compiles_for_v5e(one_chip, engine, impl):
    """The fused donated decode program at 8 slots, cache 2048, ctx_limit
    1024: the program a decode replica dispatches per chunk."""
    eng = engine("qwen3-0.6b", impl, n_slots=8, max_ctx=2048)
    vec = lambda dt: jax.ShapeDtypeStruct((8,), dt,  # noqa: E731
                                          sharding=one_chip)
    compiled = eng._build_fused(8, 1024).lower(
        _specs(eng.params, one_chip), _specs(eng.kv.caches, one_chip),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32)).compile()
    _check_fits(compiled)
    assert _has_kernel(compiled) == (impl == "pallas")
    if impl == "xla":
        _check_reads_live_lanes_in_place(compiled, eng, 1024)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= WHOLE_SLAB_DECODE_TEMP_BYTES, temp


def _check_reads_live_lanes_in_place(compiled, eng, ctx_limit: int):
    """The xla decode step reads each live lane's KV straight from the
    layer-stacked cache: no instruction outputs one layer's slab spanning
    every slot at the ctx bucket or at the whole buffer, and no copy
    outputs a whole [layers, slots, max_ctx, kv heads, head_dim] leaf."""
    cfg, B, L = eng.cfg, eng.kv.n_slots, eng.kv.max_ctx
    tail = f"{cfg.n_kv_heads},{cfg.head_dim}"
    text = compiled.as_text()
    for n in sorted({ctx_limit, L}):
        for shape in (rf"\w+\[1,{B},{n},{tail}\]", rf"\w+\[{B},{n},{tail}\]"):
            hit = re.search(rf"^.*= {shape}\{{.*$", text, re.M)
            assert hit is None, hit.group(0)[:300]
    hit = re.search(rf"^.*= \w+\[{cfg.n_layers},{B},{L},{tail}\]\{{[^}}]*\}} "
                    rf"copy\(.*$", text, re.M)
    assert hit is None, hit.group(0)[:300]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_qwen3_prefill_compiles_for_v5e(one_chip, engine, impl):
    """The turn-1 prefill program for the 512-token bucket, with its
    in-program KV write into the donated slot cache."""
    eng = engine("qwen3-0.6b", impl, n_slots=8, max_ctx=2048)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = eng._build_prefill().lower(
        _specs(eng.params, one_chip), _specs(eng.kv.caches, one_chip),
        jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one_chip),
        scalar, scalar, None).compile()
    _check_fits(compiled)
    assert _has_kernel(compiled) == (impl == "pallas")


def _deployment(arch: str):
    """(slots, max_ctx) of the benchmark's configuration for `arch`."""
    root = Path(__file__).resolve().parents[1]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    file = next(c["file"] for c in spec["configs"] if c["name"] == arch)
    dep = json.loads((root / file).read_text())["deployment"]
    return dep["slots"], dep["max_ctx"]


def _check_deployment_fits(eng, compiled):
    """The served deployment on one chip: the weights, three replicas' KV
    (one prefiller, two decoders), this program's temporaries and one
    cache leaf more for the eager KV import, under 16 GB (the benchmark's
    sizing bound, below the chip's 16 GiB)."""
    def nbytes(tree):
        return [x.size * jnp.dtype(x.dtype).itemsize
                for x in jax.tree_util.tree_leaves(tree)]
    kv = nbytes(eng.kv.caches)
    total = (sum(nbytes(eng.params)) + 3 * sum(kv)
             + compiled.memory_analysis().temp_size_in_bytes + max(kv))
    assert total < 16e9, total


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "olmo-1b"])
def test_served_decode_fits_v5e(one_chip, engine, arch):
    """The benchmark's deployment of `arch` at its published widths: the
    fused decode program at (32 steps, max_ctx), the largest a decoder
    dispatches, reads the live lanes in place and fits the chip beside the
    deployment's weights and caches."""
    slots, max_ctx = _deployment(arch)
    eng = engine(arch, "xla", n_slots=slots, max_ctx=max_ctx)
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt,  # noqa: E731
                                          sharding=one_chip)
    compiled = eng._build_fused(32, max_ctx).lower(
        _specs(eng.params, one_chip), _specs(eng.kv.caches, one_chip),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32)).compile()
    _check_fits(compiled)
    _check_reads_live_lanes_in_place(compiled, eng, max_ctx)
    _check_deployment_fits(eng, compiled)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "olmo-1b"])
def test_served_prefill_fits_v5e(one_chip, engine, arch):
    """The turn-1 prefill program at the deployment's largest prompt bucket
    (max_ctx), with its in-program KV write, fits the chip beside the
    deployment's weights and caches."""
    slots, max_ctx = _deployment(arch)
    eng = engine(arch, "xla", n_slots=slots, max_ctx=max_ctx)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = eng._build_prefill().lower(
        _specs(eng.params, one_chip), _specs(eng.kv.caches, one_chip),
        jax.ShapeDtypeStruct((max_ctx,), jnp.int32, sharding=one_chip),
        scalar, scalar, None).compile()
    _check_fits(compiled)
    _check_deployment_fits(eng, compiled)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "olmo-1b"])
def test_served_decode_lane_kernel_fits_v5e(one_chip, engine, monkeypatch,
                                            arch):
    """On the TPU the benchmark's deployment of `arch` reads the live lanes
    through the length-bounded lane kernel: the fused decode program keyed
    by its scan length alone (32 steps, no ctx bound) holds the kernel,
    copies no whole cache leaf and slices no multi-lane slab, and fits the
    chip beside the deployment's weights and caches."""
    monkeypatch.setattr(attention, "_kernel_platform", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    slots, max_ctx = _deployment(arch)
    eng = engine(arch, "xla", n_slots=slots, max_ctx=max_ctx)
    assert eng._lane_kernel
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt,  # noqa: E731
                                          sharding=one_chip)
    compiled = eng._build_fused(32, None).lower(
        _specs(eng.params, one_chip), _specs(eng.kv.caches, one_chip),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32)).compile()
    assert _has_kernel(compiled)
    _check_fits(compiled)
    _check_reads_live_lanes_in_place(compiled, eng, max_ctx)
    _check_deployment_fits(eng, compiled)
