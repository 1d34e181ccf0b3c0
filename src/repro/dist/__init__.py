"""repro.dist — tiled pairwise beta-diversity distances.

Every analysis this repo serves (PCoA, PERMANOVA, ANOSIM, Mantel,
PERMDISP) starts from an n×n distance matrix; this package owns the one
O(n²·d) step upstream of them all — turning an (n, d) feature table into
distances — and fuses it straight into the hoists the analyses consume:

* ``metrics``  — the ``Metric`` protocol (pytree dataclasses, the same
  design language as ``stats.Statistic``) with Euclidean, Bray–Curtis,
  Jaccard, Canberra, Cityblock and unweighted UniFrac instances; each
  declares a feature-chunk-additive ``accumulate`` and a ``finish``,
  which is what lets the reduce fuse into a tile sweep.
* ``tree``     — ``PhyloTree`` (a rooted tree as arrays, validated and
  ordered once on the host, read from Newick) and the device tree hoist
  that turns a table into the branch embedding UniFrac reads.
* ``unifrac_ref`` — the eager float64 UniFrac oracle the tests compare
  against.
* ``driver``   — the cache-blocked producer: row panels stream through
  the Pallas ``kernels.pairwise`` kernel (``impl="pallas"``) or the
  ``lax.map`` fallback (``impl="xla"``), emitting the condensed form
  while the operator means (row/global means of E = −½ D∘D) and the
  Mantel moments accumulate tile-by-tile — so
  ``Workspace.from_features(...)`` runs a feature-table→PCoA→PERMANOVA
  session without an n×n square distance matrix ever existing.

Quick use (the ``scipy.spatial.distance.pdist`` migration path):

    from repro.dist import pairwise_distances
    cond = pairwise_distances(table, "braycurtis", out="condensed")

Session use (the fused path — see ``repro.api.Workspace``):

    ws = Workspace.from_features(table, metric="braycurtis")
    ws.pcoa(dimensions=10); ws.permanova(grouping, 999, key=0)
"""

from repro.dist.metrics import (METRICS, BrayCurtis, Canberra, Cityblock,
                                Euclidean, Jaccard, Metric, UnweightedUniFrac,
                                get_metric, takes_tree)
from repro.dist.tree import PhyloTree, tree_hoist
from repro.dist.driver import (condensed_size, pairwise_condensed,
                               pairwise_distances)

__all__ = [
    "METRICS", "Metric", "get_metric", "takes_tree",
    "Euclidean", "BrayCurtis", "Jaccard", "Canberra", "Cityblock",
    "UnweightedUniFrac", "PhyloTree", "tree_hoist",
    "condensed_size", "pairwise_condensed", "pairwise_distances",
]
