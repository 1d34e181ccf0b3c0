"""repro.stats — distance-matrix permutation tests on one shared engine.

The paper (§4.2) accelerates the Mantel test by hoisting permutation-
invariant work out of the Monte-Carlo loop and fusing the per-permutation
remainder into a single pass over the matrix. This package applies that
recipe to the whole family of tests that dominate microbiome workloads
(cf. Sfiligoi et al. 2021, "Enabling microbiome research on personal
devices"):

* ``engine``         — the shared loop: ``Statistic`` protocol
                       (hoist/per_perm split, with the batch-fused
                       ``per_batch`` hook as the primary path — padded
                       full-size order tiles, one trace for any K),
                       p-value finishing, shard_map permutation-axis
                       distribution.
* ``permanova``      — pseudo-F from the centered Gower matrix
                       (``SS_total = tr(G)`` hoisted; per-permutation
                       gather-matmul).
* ``anosim``         — Clarke's R with the rank transform hoisted and
                       kept CONDENSED: the batched loop gathers the
                       within-indicator by closed-form triangle indexing
                       (``kernels.permute_reduce``) — no rank matrix.
* ``permdisp``       — Anderson's dispersion-homogeneity F with the whole
                       ordination hoisted (matrix-free PCoA coordinates;
                       per-permutation only centroids + distances move).
* ``partial_mantel`` — three-matrix partial correlation with ŷ
                       residualized once, square-free: both fused inner
                       products stack as rows of ONE batched
                       ``kernels.permute_reduce`` call sharing a single
                       condensed gather.

``core.mantel.mantel`` is a thin client of the same engine. Each test
ships a deliberately eager ``*_ref`` oracle mirroring scikit-bio's
multi-pass evaluation; ``benchmarks/bench_stats.py`` sweeps ref vs fused.
"""

from repro.stats.engine import (
    PermutationTestResult,
    Statistic,
    as_key,
    permutation_orders,
    permutation_test,
    permutation_test_distributed,
)
from repro.stats.anosim import AnosimStatistic, anosim, anosim_ref, \
    rank_transform, rank_transform_condensed
from repro.stats.partial_mantel import (
    PartialMantelStatistic,
    partial_mantel,
    partial_mantel_ref,
)
from repro.stats.permanova import (
    PermanovaOperatorStatistic,
    PermanovaStatistic,
    permanova,
    permanova_ref,
)
from repro.stats.permdisp import PermdispStatistic, permdisp, permdisp_ref

__all__ = [
    "PermutationTestResult", "Statistic", "as_key", "permutation_orders",
    "permutation_test", "permutation_test_distributed",
    "AnosimStatistic", "anosim", "anosim_ref", "rank_transform",
    "rank_transform_condensed",
    "PartialMantelStatistic",
    "partial_mantel", "partial_mantel_ref",
    "PermanovaOperatorStatistic", "PermanovaStatistic", "permanova",
    "permanova_ref",
    "PermdispStatistic", "permdisp", "permdisp_ref",
]
