"""Per-kernel validation: sweep shapes/dtypes in interpret mode and
assert_allclose against the pure-jnp ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lcs_reference
from repro.kernels.attention import attention_ref, flash_attention_pallas
from repro.kernels.lcs import lcs_pallas, lcs_tile_pallas, lcs_tile_ref
from repro.kernels.matmul import matmul, matmul_pallas, matmul_ref


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64, 64, 64), (128, 96, 64),
                                   (256, 128, 32), (32, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_sweep(shape, dtype):
    n, k, m = shape
    a = jax.random.normal(jax.random.PRNGKey(0), (n, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, m), jnp.float32)
    a, b = a.astype(dtype), b.astype(dtype)
    got = matmul_pallas(a, b, bn=32, bm=32, bk=32, interpret=True)
    want = matmul_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("blocks", [(16, 16, 16), (32, 64, 16), (64, 32, 64)])
def test_matmul_kernel_block_sweep(blocks):
    bn, bm, bk = blocks
    a = jax.random.normal(jax.random.PRNGKey(2), (128, 128), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(3), (128, 128), jnp.float32)
    got = matmul_pallas(a, b, bn=bn, bm=bm, bk=bk, interpret=True)
    np.testing.assert_allclose(got, matmul_ref(a, b), atol=1e-4, rtol=1e-4)


def test_matmul_ops_fallback_nondivisible():
    a = jax.random.normal(jax.random.PRNGKey(4), (17, 23), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(5), (23, 31), jnp.float32)
    np.testing.assert_allclose(matmul(a, b, interpret=True),
                               matmul_ref(a, b), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_kernel_gqa_causal(hq, hkv, causal):
    q = jax.random.normal(jax.random.PRNGKey(0), (2, hq, 64, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, hkv, 64, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, hkv, 64, 32))
    got = flash_attention_pallas(q, k, v, causal=causal, bq=32, bk=16,
                                 interpret=True)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [8, 32])
def test_attention_kernel_sliding_window(window):
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 128, 16))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 128, 16))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 128, 16))
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 bq=32, bk=32, interpret=True)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_attention_kernel_softcap_and_bf16():
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 64, 32),
                          jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 64, 32),
                          jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 64, 32),
                          jnp.float32).astype(jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, logit_cap=50.0,
                                 bq=32, bk=32, interpret=True)
    want = attention_ref(q, k, v, causal=True, logit_cap=50.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_attention_kernel_matches_model_layer():
    """Kernel == the chunked-jnp attention used by the models (the
    production lowering) — proves the two paths are interchangeable."""
    from repro.models.layers import attention as model_attention
    b, s, hq, hkv, d = 2, 64, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(9), (b, s, hq, d))
    k = jax.random.normal(jax.random.PRNGKey(10), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(11), (b, s, hkv, d))
    # the kernel takes (B, H, S, D); the models use (B, S, H, D)
    got = flash_attention_pallas(
        *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True, bq=32,
        bk=32, interpret=True).transpose(0, 2, 1, 3)
    want = model_attention(q, k, v, q_positions=jnp.arange(s),
                           k_positions=jnp.arange(s), causal=True,
                           q_chunk=16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# LCS wavefront
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile", [8, 16, 32])
def test_lcs_tile_kernel_vs_ref(tile):
    rng = np.random.default_rng(tile)
    s = jnp.array(rng.integers(0, 4, tile), jnp.int32)
    t = jnp.array(rng.integers(0, 4, tile), jnp.int32)
    top = jnp.array(rng.integers(0, 3, tile), jnp.int32)
    top = jnp.sort(top)  # borders must be monotone (valid DP rows)
    left = jnp.sort(jnp.array(rng.integers(0, 3, tile), jnp.int32))
    corner = jnp.minimum(top[:1], left[:1])
    got_b, got_r = lcs_tile_pallas(s, t, top, left, corner, interpret=True)
    want_b, want_r = lcs_tile_ref(s, t, top, left, corner)
    np.testing.assert_array_equal(got_b, want_b)
    np.testing.assert_array_equal(got_r, want_r)


@pytest.mark.parametrize("n,p", [(64, 2), (64, 4), (128, 3)])
def test_lcs_kernel_end_to_end(n, p):
    rng = np.random.default_rng(n + p)
    s = jnp.array(rng.integers(0, 4, n), jnp.int32)
    t = jnp.array(rng.integers(0, 4, n), jnp.int32)
    assert int(lcs_pallas(s, t, p, interpret=True)) == int(
        lcs_reference(s, t))
