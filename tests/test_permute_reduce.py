"""``kernels.permute_reduce`` validation: both implementations (the
Pallas kernel and its lax.scan twin) against the eager square-roundtrip
``_ref`` oracle, across odd n, non-tile-multiple m and B, trailing
chunks, and both interpret modes — plus the engine-facing properties
(identity order, stacked invariant rows, int32 refusal) — and the row
layout ``permute_reduce_rows`` with its square build ``hollow_square``
against the same oracle and the condensed kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distance_matrix import (condensed_index,
                                        condensed_to_square,
                                        random_distance_matrix,
                                        triangle_coords)
from repro.kernels import permute_reduce
from repro.kernels.permute_reduce_ops import (hollow_square,
                                              permute_reduce_rows)
from repro.kernels.permute_reduce_ref import permute_reduce_ref

KEY = jax.random.PRNGKey(7)


def _case(n, b_perms, s, seed=0):
    m = n * (n - 1) // 2
    xc = random_distance_matrix(jax.random.PRNGKey(seed), n).condensed_form()
    ys = jax.random.normal(jax.random.fold_in(KEY, seed), (s, m))
    orders = jnp.argsort(jax.random.bits(
        jax.random.fold_in(KEY, seed + 99), (b_perms, n),
        dtype=jnp.uint32), axis=-1)
    return xc, ys, orders


# --------------------------------------------------------------------------
# triangle geometry — the closed form IS the scipy layout
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n", [2, 3, 17, 64])
def test_triangle_coords_roundtrip(n):
    ii, jj = triangle_coords(n)
    iu = np.triu_indices(n, k=1)
    np.testing.assert_array_equal(np.asarray(ii), iu[0])
    np.testing.assert_array_equal(np.asarray(jj), iu[1])
    k = condensed_index(jnp.asarray(iu[0], jnp.int32),
                        jnp.asarray(iu[1], jnp.int32), n)
    np.testing.assert_array_equal(np.asarray(k), np.arange(iu[0].size))
    # symmetric in its arguments (lo/hi normalization)
    k_swapped = condensed_index(jnp.asarray(iu[1], jnp.int32),
                                jnp.asarray(iu[0], jnp.int32), n)
    np.testing.assert_array_equal(np.asarray(k_swapped), np.asarray(k))


# --------------------------------------------------------------------------
# parity vs the _ref oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("n,b_perms,s,chunk", [
    (33, 5, 1, 64),     # odd n, m=528 → trailing chunk (528 % 64 != 0)
    (17, 7, 2, 32),     # odd n AND non-multiple B, stacked rows
    (40, 3, 3, 1024),   # chunk > m: single padded chunk
    (24, 8, 2, 100),    # chunk not a multiple of 8 (geometry snaps it)
])
def test_permute_reduce_matches_ref(impl, n, b_perms, s, chunk):
    xc, ys, orders = _case(n, b_perms, s, seed=n)
    got = permute_reduce(xc, ys, orders, impl=impl, chunk=chunk,
                         interpret=True if impl == "pallas" else None)
    want = permute_reduce_ref(xc, ys, orders)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_permute_reduce_impls_agree_and_auto_interpret():
    """interpret=None auto-resolves per backend (the interpreter on this
    container's CPU) and the two impls agree on identical inputs."""
    xc, ys, orders = _case(26, 6, 2, seed=1)
    a = permute_reduce(xc, ys, orders, impl="xla")
    b = permute_reduce(xc, ys, orders, impl="pallas")   # interpret=None
    c = permute_reduce(xc, ys, orders, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def test_permute_reduce_identity_order_is_plain_dot():
    """The identity permutation reduces to <xc, ys[s]> exactly — the
    observed-statistic path of every condensed statistic."""
    n = 30
    xc, ys, _ = _case(n, 1, 2, seed=2)
    orders = jnp.arange(n, dtype=jnp.int32)[None, :]
    got = permute_reduce(xc, ys, orders, impl="xla")
    want = ys @ xc
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_permute_reduce_tiny_n_edges():
    """n=2 (m=1) and n=1 (m=0, empty triangle) don't crash or mis-shape."""
    out = permute_reduce(jnp.ones((1,)), jnp.full((1, 1), 2.0),
                         jnp.asarray([[0, 1], [1, 0]]), impl="xla")
    np.testing.assert_allclose(np.asarray(out), [[2.0, 2.0]])
    empty = permute_reduce(jnp.zeros((0,)), jnp.zeros((2, 0)),
                           jnp.zeros((3, 1), jnp.int32), impl="pallas",
                           interpret=True)
    assert empty.shape == (2, 3)
    np.testing.assert_array_equal(np.asarray(empty), 0.0)


def test_permute_reduce_validates():
    xc, ys, orders = _case(10, 2, 1, seed=3)
    with pytest.raises(ValueError, match="impl"):
        permute_reduce(xc, ys, orders, impl="cuda")
    with pytest.raises(ValueError, match="condensed length"):
        permute_reduce(xc[:-1], ys, orders)
    with pytest.raises(ValueError, match="ys must be"):
        permute_reduce(xc, ys[:, :-1], orders)
    # int32 triangle indexing refuses n past the exact bound, like
    # CondensedCenteredGramOperator
    big = jnp.zeros((2, 50000), jnp.int32)
    with pytest.raises(ValueError, match="int32"):
        permute_reduce(xc, ys, big)


def test_permute_reduce_precomputed_coords_match():
    """Passing hoisted (ii, jj) — what every statistic does — is
    bitwise the recomputed path."""
    xc, ys, orders = _case(21, 4, 1, seed=4)
    ii, jj = triangle_coords(21)
    a = permute_reduce(xc, ys, orders, ii, jj, impl="xla")
    b = permute_reduce(xc, ys, orders, impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# the row layout: whole-row gathers of the two hollow squares
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b_perms", [1, 8])
@pytest.mark.parametrize("s", [1, 2])
@pytest.mark.parametrize("n", [2, 3, 17, 64, 129])
def test_permute_reduce_rows_matches_condensed(n, s, b_perms):
    """The windowed square build is bitwise ``condensed_to_square``; the
    row-gather draws match the oracle and the condensed kernel; and the
    identity order reduces to the plain condensed dot. Both kernels sum
    in fp32 in different orders, so a draw that cancels to near zero
    carries an error of the largest draw's rounding: the tolerance is
    1e-5 of the draws' scale as well as of each draw."""
    xc, ys, orders = _case(n, b_perms, s, seed=n + s)
    xs = hollow_square(xc, n)
    np.testing.assert_array_equal(
        np.asarray(xs).view(np.uint32),
        np.asarray(condensed_to_square(xc, n)).view(np.uint32))
    ys_sq = jnp.stack([hollow_square(y, n) for y in ys])
    got = np.asarray(permute_reduce_rows(xs, ys_sq, orders))
    assert got.shape == (s, b_perms)
    for want in (permute_reduce_ref(xc, ys, orders),
                 permute_reduce(xc, ys, orders, impl="xla")):
        want = np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
    identity = jnp.arange(n, dtype=jnp.int32)[None, :]
    plain = np.asarray(ys @ xc)
    np.testing.assert_allclose(
        np.asarray(permute_reduce_rows(xs, ys_sq, identity))[:, 0], plain,
        rtol=1e-5, atol=1e-5 * np.abs(plain).max())


def test_permute_reduce_rows_validates():
    xc, ys, orders = _case(10, 2, 1, seed=3)
    xs = hollow_square(xc, 10)
    with pytest.raises(ValueError, match="xs must be"):
        permute_reduce_rows(xs[:, :-1], xs[None], orders)
    with pytest.raises(ValueError, match="ys must be"):
        permute_reduce_rows(xs, xs, orders)
