"""CenteredGramOperator + matrix-free PCoA: the operator must be an exact
drop-in for ``center_distance_matrix(D) @ X``, and the matrix-free fsvd
must match the materialized eigh oracle — the PR 2 acceptance gates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CenteredGramOperator,
                        centered_gram_matvec_distributed, pcoa,
                        random_distance_matrix)
from repro.core.centering import center_distance_matrix
from repro.kernels.center_matvec_ref import center_matvec_ref
from repro.kernels.center_ref import center_distance_matrix_ref


def _matvec_case(n, seed, k=7):
    d = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                          (n, k))
    return d, x, center_distance_matrix(d) @ x


# --------------------------------------------------------------------------
# operator vs materialized centering
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n", [7, 33, 65, 101, 128])
def test_matvec_matches_materialized_odd_n(n):
    """F@X without F, across n that are not block multiples (block=32)."""
    d, x, want = _matvec_case(n, seed=n)
    got = CenteredGramOperator.from_distance(d, block=32).matvec(x)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * max(scale, 1.0))


@pytest.mark.parametrize("n", [16, 77, 96])
def test_matvec_pallas_impl_matches(n):
    """F@X at block=32, n below, off and on a block multiple, against the
    materialised matrix."""
    d, x, want = _matvec_case(n, seed=n + 1)
    got = CenteredGramOperator.from_distance(d, block=32).matvec(x)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * max(scale, 1.0))


def _matvec_inputs(n, k, seed):
    d = random_distance_matrix(jax.random.PRNGKey(seed), n).data
    x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 7),
                          (n, k))
    return d, x


@pytest.mark.parametrize("n,k", [(16, 4), (64, 10), (77, 7), (128, 20),
                                 (200, 3)])
def test_center_matvec_matches_ref(n, k):
    """The row-strip matvec against ``center_matvec_ref``, which
    materialises the centred matrix the eager way."""
    d, x = _matvec_inputs(n, k, seed=n)
    got = CenteredGramOperator.from_distance(d, block=32).matvec(x)
    want = center_matvec_ref(d, x)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * max(scale, 1.0))


@pytest.mark.parametrize("block", [8, 16, 64])
def test_center_matvec_block_shapes(block):
    d, x = _matvec_inputs(64, 6, seed=1)
    got = CenteredGramOperator.from_distance(d, block=block).matvec(x)
    np.testing.assert_allclose(got, center_matvec_ref(d, x),
                               rtol=2e-4, atol=2e-4)


def test_center_matvec_identity_recovers_centered_matrix():
    """F @ I == F: the matvec against the materialized matrix itself."""
    n = 48
    d, _ = _matvec_inputs(n, 1, seed=2)
    got = CenteredGramOperator.from_distance(d, block=16).matvec(jnp.eye(n))
    np.testing.assert_allclose(got, center_distance_matrix_ref(d),
                               rtol=2e-4, atol=2e-4)


def test_matvec_1d_vector_roundtrip():
    d, _, _ = _matvec_case(24, seed=3)
    v = jax.random.normal(jax.random.PRNGKey(9), (24,))
    op = CenteredGramOperator.from_distance(d)
    got = op.matvec(v)
    assert got.shape == (24,)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(center_distance_matrix(d) @ v),
                               rtol=1e-4, atol=1e-4)


def test_trace_exact():
    """tr(F) from the hoisted sums == trace of the materialized matrix."""
    d = random_distance_matrix(jax.random.PRNGKey(2), 67).data
    op = CenteredGramOperator.from_distance(d)
    want = float(jnp.trace(center_distance_matrix(d)))
    assert abs(float(op.trace()) - want) < 1e-3 * max(abs(want), 1.0)
    assert float(op.trace()) > 0.0


def test_operator_crosses_jit_boundary():
    """The pytree registration: a jitted consumer caches per (shape, meta)."""
    d, x, want = _matvec_case(32, seed=5)

    @jax.jit
    def consume(op, x):
        return op.matvec(x) + op.trace()

    got = consume(CenteredGramOperator.from_distance(d, block=16), x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want) +
                               float(jnp.trace(center_distance_matrix(d))),
                               rtol=1e-4, atol=1e-3)


def test_materialize_is_the_fused_centering():
    d = random_distance_matrix(jax.random.PRNGKey(4), 40).data
    op = CenteredGramOperator.from_distance(d)
    np.testing.assert_allclose(np.asarray(op.materialize()),
                               np.asarray(center_distance_matrix(d)),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# distributed matvec (1-device mesh on CPU: exercises the shard_map path)
# --------------------------------------------------------------------------
def test_matvec_distributed_single_device_mesh():
    from jax.sharding import Mesh

    d, x, want = _matvec_case(32, seed=6)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    got = centered_gram_matvec_distributed(d, x, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# matrix-free pcoa — the acceptance gate
# --------------------------------------------------------------------------
def test_matrix_free_fsvd_matches_eigh_oracle_n512():
    """Acceptance: matrix-free fsvd coordinates match the materialized
    eigh oracle (up to per-axis sign) to ≤1e-4 relative at n=512."""
    dm = random_distance_matrix(jax.random.PRNGKey(512), 512, dim=6)
    r_eigh = pcoa(dm, dimensions=6, method="eigh")
    r_mf = pcoa(dm, dimensions=6, method="fsvd")      # default: matrix-free
    np.testing.assert_allclose(np.asarray(r_mf.eigenvalues),
                               np.asarray(r_eigh.eigenvalues), rtol=1e-4)
    scale = np.abs(np.asarray(r_eigh.coordinates)).max()
    for j in range(6):
        a = np.asarray(r_mf.coordinates[:, j])
        b = np.asarray(r_eigh.coordinates[:, j])
        assert min(np.abs(a - b).max(), np.abs(a + b).max()) <= 1e-4 * scale


def test_matrix_free_matches_materialized_fsvd():
    """Same solver, same key: operator path == materialize-then-solve."""
    dm = random_distance_matrix(jax.random.PRNGKey(20), 96, dim=5)
    key = jax.random.PRNGKey(1)
    r_mat = pcoa(dm, dimensions=5, key=key, materialize=True)
    r_mf = pcoa(dm, dimensions=5, key=key)
    np.testing.assert_allclose(np.asarray(r_mf.eigenvalues),
                               np.asarray(r_mat.eigenvalues),
                               rtol=1e-3, atol=1e-3)


def test_proportion_explained_uses_exact_total():
    """fsvd with k ≪ rank: proportions must be shares of the FULL inertia
    (operator trace), not renormalized over the top-k — the old
    ``total <= 0`` fallback's silent failure mode."""
    dm = random_distance_matrix(jax.random.PRNGKey(22), 128, dim=16)
    r = pcoa(dm, dimensions=4, method="fsvd")
    prop = np.asarray(r.proportion_explained)
    assert (prop >= 0).all()
    # 4 of 16 significant axes: the captured share must be well below 1
    assert prop.sum() < 0.9
    # and it must equal eigenvalues / exact trace
    from repro.core import CenteredGramOperator
    total = float(CenteredGramOperator.from_distance(dm.data).trace())
    np.testing.assert_allclose(
        prop, np.maximum(np.asarray(r.eigenvalues), 0.0) / total, rtol=1e-4)


def test_proportion_explained_degenerate_zero_matrix():
    """The all-zero distance matrix has zero inertia: proportions are 0,
    not NaN and not a silently renormalized top-k share."""
    from repro.core import DistanceMatrix
    dm = DistanceMatrix(jnp.zeros((12, 12)), _skip_validation=True)
    r = pcoa(dm, dimensions=3, method="fsvd")
    assert np.all(np.asarray(r.proportion_explained) == 0.0)
