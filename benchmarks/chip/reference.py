"""The plain reference: every answer of a study in float64 NumPy/SciPy.

It imports nothing of the program and takes nothing the program made:
it starts from the same feature tables and the same integer keys. The
permutation orders are regenerated from a key by their definition (the
argsort of ``jax.random.bits(PRNGKey(key), (K, n), uint32)``, ties kept
in order), so a null draw here and in the program is the statistic on
the same relabelling.

``Precision`` is the one switch between the reference and its control:
``float64`` keeps every stored operand as it is; ``bfloat16`` rounds
each stored operand (features, distances, ranks, centred vectors,
coordinates, the statistics themselves) to bfloat16, the step a later
change to the program would be tempted to take.

The statistics follow the program's definitions, which are scikit-bio's:
PERMANOVA's pseudo-F over squared distances, ANOSIM's R over average
ranks, PERMDISP's one-way F over distances to group centroids in the top
``k`` principal coordinates, Pearson's r for Mantel, and the partial
Mantel r of x and y given z, with the permutation applied to x (or to
the group labels).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = max(1, min(8, (os.cpu_count() or 2) - 1))


class Precision:
    def __init__(self, name: str = "float64"):
        if name not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name

    def __call__(self, a):
        a = np.asarray(a, dtype=np.float64)
        if self.name == "float64":
            return a
        import ml_dtypes
        return a.astype(ml_dtypes.bfloat16).astype(np.float64)


FLOAT64 = Precision("float64")


def _map(fn, items):
    items = list(items)
    if len(items) <= 1 or THREADS == 1:
        return [fn(i) for i in items]
    with ThreadPoolExecutor(THREADS) as ex:
        return list(ex.map(fn, items))


def orders(key: int, permutations: int, n: int) -> np.ndarray:
    """(K, n) permutation orders for integer ``key``, by definition."""
    import jax
    import jax.numpy as jnp
    bits = np.asarray(jax.random.bits(jax.random.PRNGKey(int(key)),
                                      (permutations, n), dtype=jnp.uint32))
    return np.argsort(bits, axis=-1, kind="stable")


def braycurtis(table, prec: Precision = FLOAT64):
    """The (n, n) Bray–Curtis matrix of the non-negative ``table`` in
    float64: Σ|x_i − x_j| / Σ(x_i + x_j) = 1 − 2·Σ_f min(x_if, x_jf) /
    (S_i + S_j), with S_i the sum of row i and 0/0 taken as 0. The sums
    of minima go feature by feature over the samples that hold the
    feature, so a sparse table costs what its non-zeros cost."""
    from scipy.sparse import csc_matrix
    x = csc_matrix(np.asarray(table)).astype(np.float64)
    x.data[:] = prec(x.data)
    n = x.shape[0]
    shared = np.zeros((n, n))
    for f in range(x.shape[1]):
        lo, hi = x.indptr[f], x.indptr[f + 1]
        if lo < hi:
            at = x.indices[lo:hi]
            v = x.data[lo:hi]
            shared[np.ix_(at, at)] += np.minimum.outer(v, v)
    sums = np.diag(shared).copy()
    total = sums[:, None] + sums[None, :]
    with np.errstate(invalid="ignore", divide="ignore"):
        square = np.where(total > 0, 1.0 - 2.0 * shared / total, 0.0)
    np.fill_diagonal(square, 0.0)
    return prec(square)


def condensed(square):
    i, j = np.triu_indices(square.shape[0], k=1)
    return square[i, j]


def onehot(codes, k):
    return (codes[..., None] == np.arange(k)).astype(np.float64)


class Reference:
    """One study's reference: its distance matrix and what every test
    hoists from it. ``groups`` are the study's labels; ``others`` the
    matrices a Mantel-family test holds fixed."""

    def __init__(self, square, groups=None, prec: Precision = FLOAT64):
        self.prec = prec
        self.d = square
        self.n = square.shape[0]
        self.m = self.n * (self.n - 1) // 2
        self.groups = None if groups is None else np.asarray(groups)
        self._cache = {}

    def _get(self, key, build):
        if key not in self._cache:
            self._cache[key] = build()
        return self._cache[key]

    # -- Mantel family -----------------------------------------------------
    def centred(self):
        """(x̄, ‖x − x̄‖, x̂ square) of the condensed distances."""
        def build():
            c = condensed(self.d)
            mean = c.mean()
            dev = c - mean
            norm = np.sqrt(dev @ dev)
            hat = np.zeros_like(self.d)
            i, j = np.triu_indices(self.n, k=1)
            hat[i, j] = self.prec(dev / norm)
            hat[j, i] = hat[i, j]
            return mean, norm, hat
        return self._get("centred", build)

    def _permuted_dots(self, order, hats):
        """Σ_{i<j} x[o_i, o_j] · h_ij for each fixed square ``h``."""
        xp = self.d[np.ix_(order, order)]
        return [0.5 * float(np.sum(xp * h)) for h in hats]

    def mantel(self, other: "Reference", order):
        mean, norm, _ = self.centred()
        _, _, yhat = other.centred()
        (dot,) = self._permuted_dots(order, [yhat])
        # x̂ would subtract x̄ · Σŷ, which is 0 to float64 rounding
        return (dot - mean * 0.5 * float(np.sum(yhat))) / norm

    def partial_mantel(self, other: "Reference", control: "Reference",
                       order):
        mean, norm, _ = self.centred()
        _, _, yhat = other.centred()
        _, _, zhat = control.centred()
        r_yz = 0.5 * float(np.sum(yhat * zhat))
        dy, dz = self._permuted_dots(order, [yhat, zhat])
        r_xy = (dy - mean * 0.5 * float(np.sum(yhat))) / norm
        r_xz = (dz - mean * 0.5 * float(np.sum(zhat))) / norm
        return (r_xy - r_xz * r_yz) / np.sqrt((1 - r_xz ** 2)
                                             * (1 - r_yz ** 2))

    # -- grouping tests ----------------------------------------------------
    def _within(self, square, codes):
        """Per group g: Σ_{i<j, both in g} square_ij, and the sizes."""
        k = int(self.groups.max()) + 1
        z = onehot(codes, k)                                  # (n, k)
        return 0.5 * np.sum(z * (square @ z), axis=0), z.sum(axis=0)

    def permanova(self, order):
        d2 = self._get("d2", lambda: self.d * self.d)
        total = self._get("ss_total", lambda: float(condensed(d2).sum()))
        k = int(self.groups.max()) + 1
        within, sizes = self._within(d2, self.groups[order])
        ss_total = total / self.n
        ss_within = float(np.sum(within / sizes))
        return (((ss_total - ss_within) / (k - 1))
                / (ss_within / (self.n - k)))

    def anosim(self, order):
        def build():
            from scipy.stats import rankdata
            r = self.prec(rankdata(condensed(self.d)))
            sq = np.zeros_like(self.d)
            i, j = np.triu_indices(self.n, k=1)
            sq[i, j] = r
            sq[j, i] = r
            return sq, float(r.sum())
        ranks, total = self._get("ranks", build)
        within, sizes = self._within(ranks, self.groups[order])
        n_within = float(np.sum(sizes * (sizes - 1) / 2))
        r_w = within.sum() / n_within
        r_b = (total - within.sum()) / (self.m - n_within)
        return (r_b - r_w) / (self.n * (self.n - 1) / 4)

    def gower_eigh(self):
        """Eigenvalues (descending) and eigenvectors of the Gower-centred
        −½D∘D, exactly."""
        def build():
            e = -0.5 * self.d * self.d
            g = e - e.mean(axis=0) - e.mean(axis=1)[:, None] + e.mean()
            w, v = np.linalg.eigh(g)
            return w[::-1], v[:, ::-1]
        return self._get("eigh", build)

    def eigenvalues(self, k):
        return self.prec(self.gower_eigh()[0][:k])

    def coordinates(self, k):
        def build():
            w, v = self.gower_eigh()
            return self.prec(v[:, :k] * np.sqrt(np.maximum(w[:k], 0.0)))
        return self._get(("coords", k), build)

    def permdisp(self, order, dimensions):
        x = self.coordinates(dimensions)
        codes = self.groups[order]
        k = int(self.groups.max()) + 1
        z = onehot(codes, k)
        sizes = z.sum(axis=0)
        centroids = (z.T @ x) / sizes[:, None]
        v = np.linalg.norm(x - centroids[codes], axis=1)
        means = (z.T @ v) / sizes
        grand = v.mean()
        ssb = float(np.sum(sizes * (means - grand) ** 2))
        ssw = float(np.sum((v - means[codes]) ** 2))
        return (ssb / (k - 1)) / (ssw / (self.n - k))


def statistic(method, ref: Reference, order, operands):
    """One statistic of ``method`` on ``order`` (identity = observed)."""
    if method == "mantel":
        return ref.mantel(operands["other"], order)
    if method == "partial_mantel":
        return ref.partial_mantel(operands["other"], operands["control"],
                                  order)
    if method == "permdisp":
        return ref.permdisp(order, operands["dimensions"])
    return getattr(ref, method)(order)


def test(method, ref: Reference, operands, key, permutations, rows=None):
    """(observed, draws) of a permutation test: the draws on the orders
    of ``key``, all of them or the rows ``rows`` of them."""
    n = ref.n
    observed = statistic(method, ref, np.arange(n), operands)
    o = orders(key, permutations, n)
    if rows is not None:
        o = o[rows]
    draws = _map(lambda order: statistic(method, ref, order, operands), o)
    return (float(ref.prec(observed)),
            ref.prec(np.asarray(draws, dtype=np.float64)))


def exceeding(observed, draws, alternative):
    draws = np.asarray(draws)
    if alternative == "two-sided":
        return int(np.sum(np.abs(draws) >= abs(observed)))
    if alternative == "greater":
        return int(np.sum(draws >= observed))
    raise ValueError(f"unknown alternative {alternative!r}")


def count_band(observed, draws, alternative, tol):
    """(lowest, highest) count of draws at least as extreme as
    ``observed`` for any observed value and draws within ``tol`` of
    these: the counts a correct float32 run may report."""
    draws = np.asarray(draws)
    if alternative == "two-sided":
        a, o = np.abs(draws), abs(observed)
        return int(np.sum(a > o + 2 * tol)), int(np.sum(a >= o - 2 * tol))
    return (int(np.sum(draws > observed + 2 * tol)),
            int(np.sum(draws >= observed - 2 * tol)))


def p_count(p_value, permutations):
    """The exceedance count behind a Monte-Carlo p-value (c+1)/(K+1)."""
    return int(round(p_value * (permutations + 1))) - 1
