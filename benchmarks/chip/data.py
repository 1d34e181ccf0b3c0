"""Feature tables made from the seed.

Two kinds of table. ``abundance_table`` is dense-ish and continuous:
log-normal abundances around a per-group profile, a fixed share of
entries zero. ``count_table`` is shaped like an OTU table: integer read
counts, most entries zero, each feature present in a share of the
samples that is heavy-tailed over the features (a few are everywhere,
most are rare) and differs between groups; ``counterpart`` is the same
samples' table over another region of the gene, with its own width.
Every number comes from ``numpy.random.default_rng`` on the run's seed,
so the same seed gives the same tables on any machine.
"""

from __future__ import annotations

import numpy as np

GROUP_SHARES = (0.6, 0.3, 0.1)
PREVALENCE_SIGMA = 1.5      # spread of log prevalence over features
GROUP_SIGMA = 1.0           # spread of a group's log prevalence about it
MAX_PREVALENCE = 0.95


def make_groups(rng, n, shares=GROUP_SHARES):
    """Labels of ``len(shares)`` skewed groups, every group present."""
    groups = rng.choice(len(shares), size=n, p=shares)
    groups[:len(shares)] = np.arange(len(shares))
    return groups.astype(np.int32)


def abundance_table(rng, groups, d, zero_share=0.7):
    """A non-negative (n, d) float32 table: log-normal abundances around a
    per-group profile, ``zero_share`` of entries zero."""
    n = groups.size
    profile = rng.normal(0.0, 1.0, size=(groups.max() + 1, d))
    logab = profile[groups] + rng.normal(0.0, 1.0, size=(n, d))
    present = rng.random((n, d)) >= zero_share
    return (np.exp(logab) * present).astype(np.float32)


def _counts(rng, mu):
    """Read counts of present entries: ceil of a log-normal about ``mu``."""
    return np.ceil(np.exp(mu + rng.normal(0.0, 1.0, size=mu.shape)))


def count_table(rng, groups, d, density, mean_log_count):
    """An (n, d) float32 table of integer read counts.

    Feature f is present in a sample of group g with probability p_gf:
    log-normal over features (``PREVALENCE_SIGMA``) and about that over
    groups (``GROUP_SIGMA``), scaled so that a sample holds ``density``
    of the features on average, capped at ``MAX_PREVALENCE``. A present
    entry's count is the ceiling of a log-normal about a per-group,
    per-feature log mean drawn about ``mean_log_count``. Every sample
    holds at least one feature."""
    n, k = groups.size, int(groups.max()) + 1
    logp = (rng.normal(0.0, PREVALENCE_SIGMA, size=d)
            + rng.normal(0.0, GROUP_SIGMA, size=(k, d)))
    w = np.exp(logp)
    p = np.minimum(density * w / w.mean(axis=1, keepdims=True),
                   MAX_PREVALENCE).astype(np.float32)
    mu = rng.normal(mean_log_count, 1.0, size=(k, d))
    table = np.zeros((n, d), np.float32)
    for g in range(k):
        rows = np.flatnonzero(groups == g)
        present = rng.random((rows.size, d), dtype=np.float32) < p[g]
        r, c = np.nonzero(present)
        table[rows[r], c] = _counts(rng, mu[g, c])
    empty = np.flatnonzero(~table.any(axis=1))
    table[empty, np.argmax(p[groups[empty]], axis=1)] = 1.0
    return table


def counterpart(rng, table, groups, d, density, mean_log_count, sigma):
    """The same samples' (n, d) count table over another region: each of
    ``table``'s features lands on a column of its own, its counts times
    log-normal noise (``sigma``) and rounded up, and the ``d`` − width
    columns left hold features of the region's own, drawn as
    ``count_table`` draws them."""
    n, width = table.shape
    if d < width:
        raise ValueError(f"a counterpart is at least as wide as its table "
                         f"({d} < {width})")
    cols = rng.permutation(d)
    out = np.zeros((n, d), np.float32)
    if d > width:
        out[:, cols[width:]] = count_table(rng, groups, d - width, density,
                                           mean_log_count)
    r, c = np.nonzero(table)
    out[r, cols[c]] = np.ceil(table[r, c]
                              * rng.lognormal(0.0, sigma, size=r.size))
    return out


def program_key(rng) -> int:
    """A PRNG seed for the program, below 2**31 whatever the run's seed."""
    return int(rng.integers(0, 2 ** 31 - 1))
