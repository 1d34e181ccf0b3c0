"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret=True)
vs the pure-jnp ref.py oracle — harness deliverable (c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distance_matrix import random_distance_matrix
from repro.kernels import (center_distance_matrix_pallas,
                           is_symmetric_and_hollow_pallas,
                           mantel_corr_pallas, rmsnorm_pallas)
from repro.kernels.center_ref import center_distance_matrix_ref
from repro.kernels.mantel_corr_ref import mantel_corr_ref
from repro.kernels.rmsnorm_ref import rmsnorm_ref
from repro.kernels.symhollow_ref import is_symmetric_and_hollow_ref


# --------------------------------------------------------------------------
# center
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n", [16, 64, 77, 128, 200])
def test_center_matches_ref(n):
    dm = random_distance_matrix(jax.random.PRNGKey(n), n).data
    got = center_distance_matrix_pallas(dm, block_m=32, block_n=32)
    want = center_distance_matrix_ref(dm)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bm,bn", [(8, 8), (16, 32), (64, 16)])
def test_center_block_shapes(bm, bn):
    dm = random_distance_matrix(jax.random.PRNGKey(1), 64).data
    got = center_distance_matrix_pallas(dm, block_m=bm, block_n=bn)
    want = center_distance_matrix_ref(dm)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_center_bf16():
    """bf16 path: centering subtracts near-equal magnitudes, so absolute
    error is O(bf16 eps · |E|); assert closeness + structure, not bitwise."""
    dm = random_distance_matrix(jax.random.PRNGKey(2), 64).data
    got = np.asarray(center_distance_matrix_pallas(
        dm.astype(jnp.bfloat16), block_m=32, block_n=32), np.float32)
    want = np.asarray(center_distance_matrix_ref(dm))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 0.05 * scale
    corr = np.corrcoef(got.ravel(), want.ravel())[0, 1]
    assert corr > 0.999


# --------------------------------------------------------------------------
# symhollow
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n", [16, 63, 128])
def test_symhollow_valid(n):
    dm = random_distance_matrix(jax.random.PRNGKey(n), n).data
    s, h = is_symmetric_and_hollow_pallas(dm, block=32)
    s_ref, h_ref = is_symmetric_and_hollow_ref(dm)
    assert bool(s) == bool(s_ref) is True
    assert bool(h) == bool(h_ref) is True


@pytest.mark.parametrize("i,j,expect_sym,expect_hollow", [
    (3, 5, False, True),      # off-diagonal asymmetry
    (60, 2, False, True),     # far block asymmetry
    (7, 7, True, False),      # diagonal violation (stays symmetric)
])
def test_symhollow_detects(i, j, expect_sym, expect_hollow):
    dm = random_distance_matrix(jax.random.PRNGKey(0), 64).data
    bad = dm.at[i, j].add(1.0)
    s, h = is_symmetric_and_hollow_pallas(bad, block=16)
    assert bool(s) == expect_sym
    assert bool(h) == expect_hollow


# --------------------------------------------------------------------------
# mantel_corr
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,k", [(32, 8), (96, 16), (50, 8)])
def test_mantel_corr_matches_ref(n, k):
    kx, ky, kp = jax.random.split(jax.random.PRNGKey(n), 3)
    x = random_distance_matrix(kx, n).data
    y = random_distance_matrix(ky, n).data
    orders = jax.vmap(lambda kk: jax.random.permutation(kk, n))(
        jax.random.split(kp, k))
    got = mantel_corr_pallas(x, y, orders, perm_batch=4, block=16)
    iu = np.triu_indices(n, k=1)
    want = mantel_corr_ref(x, np.asarray(y)[iu], orders)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_mantel_corr_identity_perm():
    """The identity permutation must reproduce the plain Pearson r."""
    from scipy.stats import pearsonr
    n = 40
    x = random_distance_matrix(jax.random.PRNGKey(3), n).data
    y = random_distance_matrix(jax.random.PRNGKey(4), n).data
    orders = jnp.arange(n)[None, :].repeat(4, axis=0)
    got = mantel_corr_pallas(x, y, orders, perm_batch=4, block=16)
    iu = np.triu_indices(n, k=1)
    want = pearsonr(np.asarray(x)[iu], np.asarray(y)[iu]).statistic
    np.testing.assert_allclose(got, np.full(4, want), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(4, 256), (2, 7, 128), (3, 5, 4, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, shape[-1:]) * 0.1).astype(dtype)
    got = rmsnorm_pallas(x, w, block_rows=4)
    want = rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
