"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py),
executed in interpret mode on CPU (the kernels target TPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode_attention
from repro.kernels.lane_decode_attention import lane_decode_attention
from repro.kernels.prefill_attention import flash_prefill_attention
from repro.kernels.rglru_kernel import rglru_pallas
from repro.kernels.rwkv6_kernel import wkv6_pallas

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def tol(dt):
    return TOLS[jnp.bfloat16] if dt == jnp.bfloat16 else TOLS[jnp.float32]


def rand(key, shape, dtype, scale=0.6):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@pytest.mark.parametrize("B,S,H,D", [(1, 128, 2, 64), (2, 256, 4, 64),
                                     (1, 512, 2, 128), (3, 128, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 96])
def test_flash_prefill_sweep(key, B, S, H, D, dtype, window):
    ks = jax.random.split(key, 3)
    q, k, v = (rand(ks[i], (B, S, H, D), dtype) for i in range(3))
    want = ref.causal_attention_ref(q, k, v, window=window)
    got = flash_prefill_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), window=window, block_q=64,
        block_k=64).transpose(0, 2, 1, 3)
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) < tol(dtype), f"err={float(err)}"


@pytest.mark.parametrize("B,S,H,Hkv,D", [(2, 256, 8, 2, 64), (1, 512, 4, 4, 64),
                                         (4, 128, 16, 2, 32),
                                         (2, 1024, 8, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(key, B, S, H, Hkv, D, dtype):
    ks = jax.random.split(key, 4)
    q = rand(ks[0], (B, H, D), dtype)
    k = rand(ks[1], (B, S, Hkv, D), dtype)
    v = rand(ks[2], (B, S, Hkv, D), dtype)
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    want = ref.decode_attention_ref(q, k, v, lens)
    got = flash_decode_attention(q, k, v, lens, block_k=128)
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    assert float(err) < tol(dtype), f"err={float(err)}"


@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("live", ["none", "one", "all"])
def test_lane_decode_matches_xla_lane_loop(key, G, layer, live):
    """The length-bounded lane kernel against the XLA lane loop it replaces
    (`models.attention.lane_decode_attention`, read up to the whole
    buffer) at layer `layer` of three: lanes at lengths 1, block_k - 1,
    block_k, block_k + 1 and max_ctx - 1, none, one or all of them live.
    Outputs agree to f32 rounding; lanes that are not live are zero."""
    from repro.configs import get_reduced
    from repro.models.attention import lane_decode_attention as xla_lanes
    B, Hkv, D, L, bk = 5, 2, 128, 64, 16
    H = G * Hkv
    ks = jax.random.split(key, 5)
    q = rand(ks[0], (B, H, D), jnp.float32)
    k_new = rand(ks[1], (B, Hkv, D), jnp.float32)
    v_new = rand(ks[2], (B, Hkv, D), jnp.float32)
    k = rand(ks[3], (3, B, L, Hkv, D), jnp.float32)
    v = rand(ks[4], (3, B, L, Hkv, D), jnp.float32)
    lens = jnp.asarray([1, bk - 1, bk, bk + 1, L - 1], jnp.int32)
    mask = {"none": [False] * B, "one": [False, False, False, True, False],
            "all": [True] * B}[live]
    live_a = jnp.asarray(mask)
    got = lane_decode_attention(q, k_new, v_new, k, v, layer, lens, live_a,
                                block_k=bk)
    want = xla_lanes(get_reduced("qwen3-0.6b"), q[:, None], k_new[:, None],
                     v_new[:, None], k, v, (layer,), lens, live_a)[:, 0]
    err = jnp.max(jnp.abs(got - want))
    assert float(err) < TOLS[jnp.float32], f"err={float(err)}"
    dead = ~jnp.asarray(mask)
    assert not bool(jnp.any(got[dead] != 0))


@pytest.mark.parametrize("Hkv", [2, 4])
def test_lane_decode_bf16_head_pairs(key, Hkv):
    """bf16 KV, where one 32-bit row holds a pair of KV heads: each head
    reads its own half. Against the decode oracle over the same cache with
    the new entry placed at each lane's length."""
    B, G, D, L, bk = 3, 2, 128, 64, 16
    ks = jax.random.split(key, 5)
    q = rand(ks[0], (B, G * Hkv, D), jnp.bfloat16)
    k_new = rand(ks[1], (B, Hkv, D), jnp.bfloat16)
    v_new = rand(ks[2], (B, Hkv, D), jnp.bfloat16)
    k = rand(ks[3], (2, B, L, Hkv, D), jnp.bfloat16)
    v = rand(ks[4], (2, B, L, Hkv, D), jnp.bfloat16)
    lens = jnp.asarray([5, 33, 63], jnp.int32)
    got = lane_decode_attention(q, k_new, v_new, k, v, 1, lens,
                                jnp.ones(B, bool), block_k=bk)
    b = jnp.arange(B)
    want = ref.decode_attention_ref(q, k[1].at[b, lens].set(k_new),
                                    v[1].at[b, lens].set(v_new), lens + 1)
    err = jnp.max(jnp.abs(got - want.astype(jnp.float32)))
    assert float(err) < tol(jnp.bfloat16), f"err={float(err)}"


@pytest.mark.parametrize("B,S,H,hs", [(1, 64, 2, 16), (2, 128, 3, 16),
                                      (1, 256, 2, 32), (2, 64, 1, 64)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_wkv6_sweep(key, B, S, H, hs, chunk):
    ks = jax.random.split(key, 6)
    r = rand(ks[0], (B, S, H, hs), jnp.float32, 0.5)
    k = rand(ks[1], (B, S, H, hs), jnp.float32, 0.5)
    v = rand(ks[2], (B, S, H, hs), jnp.float32, 0.5)
    logw = -jnp.exp(rand(ks[3], (B, S, H, hs), jnp.float32, 0.5))
    u = rand(ks[4], (H, hs), jnp.float32, 0.3)
    s0 = rand(ks[5], (B, H, hs, hs), jnp.float32, 0.2)
    y_ref, sT_ref = ref.wkv6_ref(r, k, v, logw, u, s0)
    y, sT = wkv6_pallas(r, k, v, logw, u, s0, chunk=chunk)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 5e-5
    assert float(jnp.max(jnp.abs(sT - sT_ref))) < 5e-5


@pytest.mark.parametrize("B,S,W", [(1, 128, 64), (2, 256, 128), (1, 512, 32)])
@pytest.mark.parametrize("chunk,block_w", [(64, 32), (128, 64)])
def test_rglru_sweep(key, B, S, W, chunk, block_w):
    if chunk > S or block_w > W:
        pytest.skip("block exceeds dims")
    ks = jax.random.split(key, 3)
    la = -jnp.exp(rand(ks[0], (B, S, W), jnp.float32, 0.3))
    b = rand(ks[1], (B, S, W), jnp.float32, 0.5)
    h0 = rand(ks[2], (B, W), jnp.float32, 0.2)
    h_ref, hT_ref = ref.rglru_ref(la, b, h0)
    h, hT = rglru_pallas(la, b, h0, chunk=chunk, block_w=block_w)
    assert float(jnp.max(jnp.abs(h - h_ref))) < 1e-5
    assert float(jnp.max(jnp.abs(hT - hT_ref))) < 1e-5


def test_model_chunked_wkv_matches_kernel_oracle(key):
    """The model-side chunked WKV6 and the Pallas kernel agree with the
    step-recurrence oracle — three independent implementations."""
    from repro.models.recurrent import wkv6_chunked
    B, S, H, hs = 2, 96, 2, 16
    ks = jax.random.split(key, 6)
    r = rand(ks[0], (B, S, H, hs), jnp.float32, 0.5)
    k = rand(ks[1], (B, S, H, hs), jnp.float32, 0.5)
    v = rand(ks[2], (B, S, H, hs), jnp.float32, 0.5)
    logw = -jnp.exp(rand(ks[3], (B, S, H, hs), jnp.float32, 0.5))
    u = rand(ks[4], (H, hs), jnp.float32, 0.3)
    s0 = rand(ks[5], (B, H, hs, hs), jnp.float32, 0.2)
    y0, s0T = ref.wkv6_ref(r, k, v, logw, u, s0)
    y1, s1T = wkv6_pallas(r, k, v, logw, u, s0, chunk=32)
    y2, s2T = wkv6_chunked(r, k, v, logw, u, s0, chunk=24)  # uneven chunk
    assert float(jnp.max(jnp.abs(y1 - y0))) < 5e-5
    assert float(jnp.max(jnp.abs(y2 - y0))) < 5e-5
    assert float(jnp.max(jnp.abs(s1T - s0T))) < 5e-5
    assert float(jnp.max(jnp.abs(s2T - s0T))) < 5e-5


def test_ops_dispatch(key):
    from repro.kernels import ops
    B, S, H, D = 1, 128, 2, 64
    ks = jax.random.split(key, 3)
    q, k, v = (rand(ks[i], (B, S, H, D), jnp.float32) for i in range(3))
    a = ops.prefill_attention(q, k, v, impl="pallas")
    b = ops.prefill_attention(q, k, v, impl="xla")
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_ops_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Native on TPU, interpreted on CPU, refused anywhere else: a kernel
    never falls back to interpret mode on an accelerator in silence."""
    from repro.kernels import ops
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
