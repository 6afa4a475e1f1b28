"""Launch-layer logic that doesn't need 512 devices: cell support rules,
the HLO collective parser, the compile-cache helper and the engine build
shared by the serving launcher and chip_smoke.py."""
import jax
import numpy as np
import pytest

from repro.configs import ASSIGNED, SHAPES, get_reduced
from repro.launch import compile_cache
from repro.launch.dryrun import parse_collectives
from repro.launch.serve import build_engine
from repro.launch.specs import cell_supported


def test_long_500k_support_rules():
    ok = {a for a in ASSIGNED if cell_supported(a, "long_500k")[0]}
    assert ok == {"rwkv6-3b", "recurrentgemma-9b"}
    # gemma3 is excluded by its published 128k max context, not by attention
    sup, reason = cell_supported("gemma3-12b", "long_500k")
    assert not sup and "max_seq" in reason


def test_all_other_cells_supported():
    for a in ASSIGNED:
        for s in SHAPES:
            if s == "long_500k":
                continue
            assert cell_supported(a, s)[0], (a, s)


def test_collective_parser():
    hlo = """
  %ar = bf16[16,128,512]{2,1,0} all-reduce(bf16[16,128,512] %x), replica_groups={}
  %ag.1 = f32[256,1024]{1,0} all-gather(f32[16,1024] %y), dimensions={0}
  %p = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-to-all(%a, %b)
  %cp = u32[4]{0} collective-permute(u32[4] %z)
  %not_a_collective = f32[2]{0} add(f32[2] %a, f32[2] %b)
"""
    totals, counts = parse_collectives(hlo)
    assert counts["all-reduce"] == 1 and totals["all-reduce"] == 16*128*512*2
    assert counts["all-gather"] == 1 and totals["all-gather"] == 256*1024*4
    assert counts["all-to-all"] == 1 and totals["all-to-all"] == 2*8*8*2
    assert counts["collective-permute"] == 1 and totals["collective-permute"] == 16
    assert sum(counts.values()) == 4


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # same every call


def test_build_engine_deployment():
    cfg = get_reduced("qwen3-0.6b")
    srv = build_engine(cfg, n_slots=2, max_ctx=128, seed=3,
                       strict_accounting=True)
    reps = [srv.replicas[i] for i in sorted(srv.replicas)]
    assert [r.role for r in reps] == ["prefill", "decode", "decode"]
    assert all(r.kv.n_slots == 2 and r.kv.max_ctx == 128 for r in reps)
    assert all(r.params is reps[0].params for r in reps)
    assert srv.strict_accounting
    leaf = jax.tree_util.tree_leaves(reps[0].params)[-1]
    again = build_engine(cfg, n_slots=2, max_ctx=128, seed=3)
    other = build_engine(cfg, n_slots=2, max_ctx=128, seed=4)
    same = jax.tree_util.tree_leaves(again.replicas[0].params)[-1]
    diff = jax.tree_util.tree_leaves(other.replicas[0].params)[-1]
    assert leaf.dtype == np.dtype(cfg.dtype)
    assert np.array_equal(leaf, same) and not np.array_equal(leaf, diff)


def test_weights_from_seed_match_across_processes():
    """One seed gives one set of weights in every process, whatever
    Python's per-process string-hash salt is."""
    import os
    import subprocess
    import sys
    code = ("import jax, numpy as np; from repro.configs import get_reduced;"
            "from repro.models import build_model;"
            "p = build_model(get_reduced('qwen3-0.6b')).init("
            "jax.random.PRNGKey(5));"
            "print(repr(float(sum(np.abs(np.asarray(l, np.float64)).sum()"
            " for l in jax.tree_util.tree_leaves(p)))))")
    sums = []
    for salt in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": salt, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(sys.path)}
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300,
                             check=True)
        sums.append(out.stdout.strip().splitlines()[-1])
    assert sums[0] == sums[1]
