"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

interpret=True executes the kernel body on CPU - validating the block
decomposition, index maps, masking and online-softmax algebra. The Mosaic
lowering is checked by tests/test_tpu_compile.py (compiled for a described
TPU, no chip needed) and run natively by chip_smoke.py on a TPU.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the hypothesis extra")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.chunk_reduce.ops import chunk_reduce
from repro.kernels.chunk_reduce.ref import chunk_reduce_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.wkv.ops import wkv
from repro.kernels.wkv.ref import wkv_ref

RNG = np.random.default_rng(7)


# ----------------------------------------------------------------------------
# chunk_reduce
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("w", [1, 2, 7, 16])
@pytest.mark.parametrize("n", [128, 1000, 4096, 5001])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_chunk_reduce_sweep(w, n, dtype):
    x = jnp.asarray(RNG.standard_normal((w, n)), dtype)
    out = chunk_reduce(x, block=1024, interpret=True)
    ref = chunk_reduce_ref(x)
    tol = 1e-6 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_chunk_reduce_fp32_accumulation():
    """bf16 inputs must accumulate in fp32 (W large, catastrophic in bf16)."""
    w, n = 16, 512
    x = jnp.full((w, n), 1.0 + 1e-3, jnp.bfloat16)
    out = chunk_reduce(x, block=256, interpret=True, out_dtype=jnp.float32)
    expect = np.float32(w) * np.asarray(x[0], np.float32)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-3)


@settings(max_examples=10, deadline=None)
@given(w=st.integers(1, 8), n=st.integers(1, 2000),
       block=st.sampled_from([128, 256, 1024]))
def test_chunk_reduce_property(w, n, block):
    x = jnp.asarray(np.random.default_rng(n).standard_normal((w, n)),
                    jnp.float32)
    out = chunk_reduce(x, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(chunk_reduce_ref(x)),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    # (B, Sq, Skv, H, KV, hd)
    (1, 32, 32, 2, 2, 16),
    (2, 64, 64, 4, 2, 32),     # GQA
    (1, 48, 48, 4, 1, 32),     # MQA
    (2, 40, 40, 2, 2, 8),      # non-multiple of block
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, dtype):
    B, Sq, Skv, H, KV, hd = shape
    q = jnp.asarray(RNG.standard_normal((B, Sq, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Skv, KV, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Skv, KV, hd)), dtype)
    out = flash_attention(q, k, v, causal=True, bq=16, bkv=16,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [8, 24, 1000])
def test_flash_attention_window(window):
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          bq=16, bkv=16, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_noncausal():
    B, S, H, KV, hd = 1, 32, 2, 2, 16
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, bq=16, bkv=16,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_matches_model_chunked_path():
    """The kernel and the model's chunked-jnp path agree (same oracle)."""
    from repro.models.attention import chunked_attention
    B, S, H, KV, hd = 1, 64, 4, 2, 16
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, bq=16, bkv=16,
                        interpret=True)
    b = chunked_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-5, atol=3e-5)


# ----------------------------------------------------------------------------
# wkv
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 16, 2, 8), (2, 33, 3, 16),
                                   (1, 64, 1, 32)])
def test_wkv_sweep(shape):
    B, S, H, hd = shape
    rng = np.random.default_rng(sum(shape))
    r, k, v = [jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
               for _ in range(3)]
    w = jnp.asarray(rng.uniform(0.2, 0.99, (B, S, H, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    out, st = wkv(r, k, v, w, u, interpret=True)
    ro, rs = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(rs),
                               rtol=1e-5, atol=1e-5)


def test_wkv_state_chaining():
    """Processing a sequence in two kernel calls chained through the state
    equals one call - the property the serving path relies on."""
    B, S, H, hd = 1, 32, 2, 8
    rng = np.random.default_rng(0)
    r, k, v = [jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
               for _ in range(3)]
    w = jnp.asarray(rng.uniform(0.5, 0.99, (B, S, H, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    full, st_full = wkv(r, k, v, w, u, interpret=True)
    h1, st1 = wkv(r[:, :16], k[:, :16], v[:, :16], w[:, :16], u,
                  interpret=True)
    h2, st2 = wkv(r[:, 16:], k[:, 16:], v[:, 16:], w[:, 16:], u,
                  state0=st1, interpret=True)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(full[:, 16:]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               rtol=1e-5, atol=1e-5)
