"""PCoA's randomized solve (fsvd) in float64, for the output check of a
cell that ordinates by fsvd.

Like ``reference.py`` it imports nothing of the program: it starts from
the reference's distance matrix and the study's integer key. The solve
is the randomized range finder with power iterations (Halko, Martinsson
and Tropp, SIAM Review 53:217, 2011) that the configuration's
``pcoa_method: fsvd`` names, at the program's documented sketch: k +
``OVERSAMPLE`` Gaussian columns, ``POWER_ITERS`` power iterations, each
followed by a QR, and the exact eigensolve of the projection QᵀAQ, A
being the Gower-centred −½D∘D. The sketch is regenerated from the key
by its definition, ``jax.random.normal(PRNGKey(key), (n, k +
OVERSAMPLE), float32)``, as ``reference.orders`` regenerates the
permutation orders. So the program's eigenvalues and these are the Ritz
values of the same subspace and differ by rounding alone, on every axis
asked for; the exact eigenvalues differ from both by how far the
subspace is from converged, which rounding cannot tell apart.

``Precision`` rounds each stored operand: the centred matrix, the
sketch, each orthonormal basis and the eigenvalues.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import FLOAT64, Precision

OVERSAMPLE = 10
POWER_ITERS = 2


def sketch(key: int, n: int, columns: int) -> np.ndarray:
    """The (n, columns) Gaussian sketch of integer ``key``, by
    definition."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.random.normal(jax.random.PRNGKey(int(key)),
                                        (n, columns), dtype=jnp.float32),
                      dtype=np.float64)


def gower(square) -> np.ndarray:
    """The Gower-centred −½D∘D of the (n, n) distance matrix."""
    e = -0.5 * square * square
    return e - e.mean(axis=0) - e.mean(axis=1)[:, None] + e.mean()


def eigenvalues(square, key: int, k: int,
                prec: Precision = FLOAT64) -> np.ndarray:
    """The top ``k`` eigenvalues (descending) of the fsvd solve of
    ``square`` on the sketch of ``key``."""
    n = square.shape[0]
    a = prec(gower(np.asarray(square, dtype=np.float64)))
    q = prec(sketch(key, n, min(k + OVERSAMPLE, n)))
    for _ in range(1 + POWER_ITERS):
        q = prec(np.linalg.qr(a @ q)[0])
    t = q.T @ (a @ q)
    w = np.linalg.eigvalsh(0.5 * (t + t.T))
    return prec(w[::-1][:k])
