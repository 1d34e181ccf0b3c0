"""Property-based tests (hypothesis) on the system's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; skip on clean envs")
from hypothesis import given, settings, strategies as st

from repro.core.centering import (center_distance_matrix,
                                  center_distance_matrix_ref)
from repro.core.distance_matrix import random_distance_matrix
from repro.core.operators import CenteredGramOperator
from repro.core.validation import is_symmetric_and_hollow
from repro.kernels import center_distance_matrix_pallas, rmsnorm_pallas
from repro.kernels.rmsnorm_ref import rmsnorm_ref
from repro.optim.compression import dequantize_int8, quantize_int8

_settings = dict(max_examples=20, deadline=None)


@given(n=st.integers(4, 80), seed=st.integers(0, 2**30))
@settings(**_settings)
def test_centering_annihilates_means(n, seed):
    dm = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    f = np.asarray(center_distance_matrix(dm))
    assert np.abs(f.mean(0)).max() < 1e-3
    assert np.abs(f.mean(1)).max() < 1e-3
    assert np.abs(f - f.T).max() < 1e-4


@given(n=st.integers(4, 64), seed=st.integers(0, 2**30))
@settings(**_settings)
def test_centering_idempotent_on_centered(n, seed):
    """Gower centering of an already-centered Gram matrix: applying the
    double-centering projector twice equals once (P A P is a projection)."""
    dm = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    f1 = center_distance_matrix_ref(dm)
    # re-center f1's "distance" interpretation is nonsense; instead check
    # the projector identity directly: centering the matrix of sqrt(-2 f)
    # is out of domain, so verify P f1 P == f1 (f1 already row/col centered)
    n_ = f1.shape[0]
    ones = jnp.ones((n_, n_)) / n_
    p = jnp.eye(n_) - ones
    np.testing.assert_allclose(p @ f1 @ p, f1, atol=1e-3)


@given(n=st.integers(4, 48), seed=st.integers(0, 2**30),
       scale=st.floats(0.1, 10.0))
@settings(**_settings)
def test_centering_scales_quadratically(n, seed, scale):
    """D → sD implies F → s²F (E = -D²/2 is quadratic, centering linear)."""
    dm = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    f1 = np.asarray(center_distance_matrix(dm))
    f2 = np.asarray(center_distance_matrix(dm * scale))
    np.testing.assert_allclose(f2, f1 * scale**2, rtol=2e-3, atol=2e-3)


@given(n=st.integers(4, 97), seed=st.integers(0, 2**30),
       k=st.integers(1, 12), block=st.sampled_from([8, 16, 32]))
@settings(**_settings)
def test_operator_matvec_equals_materialized_any_shape(n, seed, k, block):
    """CenteredGramOperator.matvec == center_distance_matrix(D) @ X to
    ≤1e-5 relative, across odd n (non-multiples of the block)."""
    dm = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, k))
    op = CenteredGramOperator.from_distance(dm, block=block)
    want = np.asarray(center_distance_matrix(dm) @ x)
    got = np.asarray(op.matvec(x))
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
    # and the hoisted trace is the materialized trace
    tr = float(jnp.trace(center_distance_matrix(dm)))
    assert abs(float(op.trace()) - tr) <= 1e-5 * max(abs(tr), 1.0)


@given(n=st.integers(4, 48), seed=st.integers(0, 2**30))
@settings(**_settings)
def test_pallas_center_equals_jnp_any_shape(n, seed):
    dm = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    got = center_distance_matrix_pallas(dm, block_m=16, block_n=16)
    want = center_distance_matrix_ref(dm)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@given(n=st.integers(3, 40), seed=st.integers(0, 2**30),
       i=st.integers(0, 39), j=st.integers(0, 39))
@settings(**_settings)
def test_validation_detects_any_single_asymmetry(n, seed, i, j):
    i, j = i % n, j % n
    dm = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    bad = dm.at[i, j].add(1.0)
    s, h = is_symmetric_and_hollow(bad)
    if i == j:
        assert bool(h) is False
    else:
        assert bool(s) is False


@given(seed=st.integers(0, 2**30), rows=st.integers(1, 9),
       d=st.sampled_from([8, 32, 128]), c=st.floats(0.5, 4.0))
@settings(**_settings)
def test_rmsnorm_scale_invariance(seed, rows, d, c):
    """rmsnorm(c·x) == rmsnorm(x) up to fp tolerance (for c > 0)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (rows, d)) + 0.1
    w = jax.random.normal(kw, (d,)) * 0.1
    a = rmsnorm_pallas(x, w, block_rows=4)
    b = rmsnorm_pallas(x * c, w, block_rows=4)
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(a, rmsnorm_ref(x, w), rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 2**30), shape=st.sampled_from([(8,), (4, 16)]))
@settings(**_settings)
def test_int8_quantization_error_bound(seed, shape):
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), shape))
    q, scale = quantize_int8(jnp.asarray(g))
    back = np.asarray(dequantize_int8(q, scale))
    # max error is half a quantization step
    assert np.abs(back - g).max() <= float(scale) * 0.5 + 1e-7


@given(seed=st.integers(0, 2**30))
@settings(max_examples=10, deadline=None)
def test_error_feedback_is_lossless_in_expectation(seed):
    """Accumulated (quantized + error) over steps equals the true sum."""
    key = jax.random.PRNGKey(seed)
    true_sum = np.zeros(32, np.float32)
    sent_sum = np.zeros(32, np.float32)
    err = jnp.zeros(32)
    for t in range(8):
        g = jax.random.normal(jax.random.fold_in(key, t), (32,))
        true_sum += np.asarray(g)
        gf = g + err
        q, s = quantize_int8(gf)
        sent = dequantize_int8(q, s)
        err = gf - sent
        sent_sum += np.asarray(sent)
    # residual error is bounded by one quantization step, not accumulated
    assert np.abs(true_sum - sent_sum).max() <= float(s) + 1e-6


@given(n=st.integers(3, 48), seed=st.integers(0, 2**30))
@settings(**_settings)
def test_hoisted_norm_is_permutation_invariant(n, seed):
    """The §4.2 hoist is sound: a row/column permutation only reorders
    the condensed entries, so the hoisted mean/norm of the permuted
    matrix equal the ones computed once outside the loop — and the
    closed-form triangle gather produces exactly that reordering."""
    from repro.core.distance_matrix import condensed_index, triangle_coords
    from repro.core.mantel import condensed_moments_vec

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    dm = random_distance_matrix(k1, n)
    xc = dm.condensed_form()
    order = jax.random.permutation(k2, n)
    ii, jj = triangle_coords(n)
    o = order.astype(jnp.int32)
    xp_c = xc[condensed_index(o[ii], o[jj], n)]  # permuted condensed
    # ...is the same multiset as the square roundtrip's condensed form
    want = dm.permute(np.asarray(order), condensed=True)
    np.testing.assert_allclose(np.asarray(xp_c), np.asarray(want),
                               rtol=0, atol=0)
    # ⇒ the hoisted moments are permutation-invariant (fp tolerance:
    # the reduction ORDER differs between the two layouts)
    a = condensed_moments_vec(xc)
    b = condensed_moments_vec(xp_c)
    np.testing.assert_allclose(float(a["norm"]), float(b["norm"]),
                               rtol=1e-4)
    assert abs(float(jnp.sum(b["hat"]))) < 1e-3
