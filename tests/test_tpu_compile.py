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

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.engine.replica import ReplicaEngine  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.decode_attention import flash_decode_attention  # noqa: E402
from repro.kernels.prefill_attention import flash_prefill_attention  # noqa: E402
from repro.models.model import Model  # noqa: E402

V5E_HBM_BYTES = 16 * 2**30


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
def qwen3_engine(monkeypatch):
    """A full-width qwen3-0.6b replica holding shapes, not arrays: params
    are the model skeleton and the slot cache its cache skeleton, so the
    engine's own program builders trace at published widths without
    allocating 1.2 GB of weights and the KV buffers."""
    def factory(attention_impl: str, n_slots: int, max_ctx: int):
        if attention_impl == "pallas":
            monkeypatch.setattr(ops, "_interpret", lambda: False)
        monkeypatch.setattr(Model, "init_cache",
                            lambda self, b, c: self.cache_skeleton(b, c))
        cfg = get_config("qwen3-0.6b")
        return ReplicaEngine(cfg, Model(cfg).skeleton(), n_slots=n_slots,
                             max_ctx=max_ctx, attention_impl=attention_impl)
    return factory


def _check_fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_qwen3_decode_step_compiles_for_v5e(one_chip, qwen3_engine, impl):
    """The fused donated decode program at 8 slots, cache 2048, ctx_limit
    1024: the program a decode replica dispatches per chunk."""
    eng = qwen3_engine(impl, n_slots=8, max_ctx=2048)
    vec = lambda dt: jax.ShapeDtypeStruct((8,), dt,  # noqa: E731
                                          sharding=one_chip)
    compiled = eng._build_fused(8, 1024).lower(
        _specs(eng.params, one_chip), _specs(eng.kv.caches, one_chip),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32)).compile()
    _check_fits(compiled)
    assert _has_kernel(compiled) == (impl == "pallas")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_qwen3_prefill_compiles_for_v5e(one_chip, qwen3_engine, impl):
    """The turn-1 prefill program for the 512-token bucket, with its
    in-program KV write into the donated slot cache."""
    eng = qwen3_engine(impl, n_slots=8, max_ctx=2048)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = eng._build_prefill().lower(
        _specs(eng.params, one_chip), _specs(eng.kv.caches, one_chip),
        jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one_chip),
        scalar, scalar, None).compile()
    _check_fits(compiled)
    assert _has_kernel(compiled) == (impl == "pallas")
