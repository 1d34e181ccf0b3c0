"""Eager oracle for the batched permuted-gather-reduce.

Deliberately takes the long way round (the PR-4 square-gather loop shape):
per permutation, rebuild the full permuted square ``X[o][:, o]``, extract
its condensed triangle, and dot it against every streamed invariant row.
``permute_reduce`` and ``permute_reduce_rows`` must agree with this to
fp tolerance — it is the ground truth that the closed-form triangle indexing
``xc[k(order[i], order[j])]`` really is the condensed form of the permuted
matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance_matrix import condensed_to_square


def permute_reduce_ref(xc: jax.Array, ys: jax.Array,
                       orders: jax.Array) -> jax.Array:
    """out[s, b] = <condensed(X[orders[b]][:, orders[b]]), ys[s]>.

    xc: (m,) condensed X. ys: (S, m) streamed invariants. orders: (B, n)
    with m = n(n-1)/2. Returns (S, B) float like ``xc``.
    """
    b_perms, n = orders.shape
    x_sq = np.asarray(condensed_to_square(xc, n))
    ys_np = np.asarray(ys, dtype=np.float64)
    iu = np.triu_indices(n, k=1)
    out = np.zeros((ys_np.shape[0], b_perms))
    for b in range(b_perms):
        o = np.asarray(orders[b])
        xp_c = x_sq[o][:, o][iu]
        out[:, b] = ys_np @ xp_c
    return jnp.asarray(out, dtype=xc.dtype)
