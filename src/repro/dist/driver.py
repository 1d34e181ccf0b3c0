"""Cache-blocked pairwise-distance driver with fused hoist accumulation.

This is the subsystem's tentpole move: the (n, d) feature table becomes
condensed distances **panel by panel**, and every downstream O(n²) hoist
that can be expressed as a running sum is accumulated *while each panel
is resident* — the paper's "compute while the data is already in cache"
argument applied one level upstream of the analyses:

* the **condensed** form (scipy ``pdist`` layout) is emitted per panel:
  the upper-triangle entries of row panel [i0, i1) occupy one contiguous
  condensed range, gathered straight out of the (b, n) strip;
* the **operator means** — row/global means of E = −½ D∘D, exactly what
  ``CenteredGramOperator.from_distance`` hoists from a square D — come
  from each strip's row sums of D², so ``Workspace.from_features`` can
  run matrix-free PCoA/PERMANOVA without a square n×n ever existing.

Peak memory is one (block, n) strip plus the (m,) condensed output,
m = n(n−1)/2 — the square matrix is never allocated. A panel has two
bodies, chosen by the metric's type:

* an elementwise metric runs ``_panel_xla``, the ``lax.map`` row-panel
  sweep — sub-panels of rows stream against the full table with the
  metric's reduce feature-chunked, so the broadcast term stays
  (rows, n, chunk)-bounded;
* a product metric (``metrics.is_product``: unweighted UniFrac) runs
  its ``accumulate`` once over the whole feature axis for the whole
  (block, n) panel, one matrix product that XLA tiles itself;
  ``feature_block`` does not apply to it.
"""

from __future__ import annotations

from functools import partial
import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.metrics import (Metric, get_metric, is_product, merge_acc,
                                takes_tree)
from repro.dist.tree import PhyloTree, tree_hoist
from repro.kernels.dispatch import clamp_block
from repro.obs.compile import note_run, note_trace
from repro.obs.trace import current_obs

_DEFAULT_BLOCK = 256
_DEFAULT_FEATURE_BLOCK = 128
_ROW_CHUNK = 8


def condensed_size(n: int) -> int:
    """m = n(n−1)/2, the scipy ``pdist`` condensed length."""
    return n * (n - 1) // 2


def _panel_condensed_indices(n: int, i0: int, i1: int) -> np.ndarray:
    """Local flat indices into a (b, n) row strip for the condensed
    entries owned by rows [i0, i1) — one contiguous condensed range
    (row r owns positions [r(2n−r−1)/2, …), each a run of n−1−r)."""
    return np.concatenate(
        [(r - i0) * n + np.arange(r + 1, n) for r in range(i0, i1)]
        or [np.zeros(0, dtype=np.int64)]).astype(np.int32)


def _panel_xla(xi: jax.Array, x: jax.Array, metric: Metric,
               feature_block: int) -> jax.Array:
    """lax.map row-panel fallback: (bm, d) × (n, d) → (bm, n).

    Rows stream in sub-panels so each step's broadcast term is bounded at
    (row_chunk, n, feature_block); the feature axis is chunked by static
    slicing (no padding needed — the trailing short chunk is just a
    smaller slice in the same trace).
    """
    bm, d = xi.shape
    rb = next(r for r in range(min(_ROW_CHUNK, bm), 0, -1) if bm % r == 0)
    sub = xi.reshape(bm // rb, rb, d)

    def one(p):
        acc = None
        for c0 in range(0, d, feature_block):
            part = metric.accumulate(p[:, c0:c0 + feature_block],
                                     x[:, c0:c0 + feature_block])
            acc = part if acc is None else merge_acc(acc, part)
        return metric.finish(acc)

    return jax.lax.map(one, sub).reshape(bm, x.shape[0])


def panel_feature_block(metric: Metric, width: int,
                        feature_block: int) -> int:
    """The feature chunk that keys a panel program on a ``width``-wide
    input: a product metric's panel reduces the whole width at once;
    every other panel takes ``feature_block`` as asked."""
    return width if is_product(metric) else feature_block


def _panel_signature(xi, x, metric: Metric, feature_block: int) -> tuple:
    """What keys a ``_panel_stats`` program, for its trace and run notes."""
    return (tuple(xi.shape), tuple(x.shape), metric.name, feature_block)


def _run_panel(xi, x, metric: Metric, feature_block: int):
    """``_panel_stats``, its execution counted (``note_run``)."""
    feature_block = panel_feature_block(metric, x.shape[1], feature_block)
    note_run(_panel_stats, _panel_signature(xi, x, metric, feature_block))
    return _panel_stats(xi, x, metric=metric, feature_block=feature_block)


@partial(jax.jit, static_argnames=("metric", "feature_block"))
def _panel_stats(xi: jax.Array, x: jax.Array, *, metric: Metric,
                 feature_block: int):
    """One row strip + its fused row sums: (strip, Σ_j d²).

    The row sums ride the same jit region as the strip compute, so XLA
    fuses them into the panel sweep — the hoist costs no extra pass. A
    product metric's strip is one ``accumulate`` over the whole panel
    and feature axis, an elementwise metric's ``_panel_xla``. Profiler
    scopes: ``dist.accumulate`` (the strip) and ``dist.rowsums``."""
    note_trace("dist.panel_stats",
               _panel_signature(xi, x, metric, feature_block),
               _panel_stats, (xi, x),
               {"metric": metric, "feature_block": feature_block})
    with jax.named_scope("dist.accumulate"):
        if is_product(metric):
            strip = metric.finish(metric.accumulate(xi, x))
        else:
            strip = _panel_xla(xi, x, metric, feature_block)
    with jax.named_scope("dist.rowsums"):
        return strip, jnp.sum(strip * strip, axis=1)


def check_tree(metric: Metric, tree, width: int) -> None:
    """Raise ``ValueError`` unless ``tree`` suits ``metric`` on a table
    of ``width`` feature columns: a tree metric needs a tree with one tip
    per column, any other metric takes none."""
    if not takes_tree(metric):
        if tree is not None:
            raise ValueError(f"metric {metric.name!r} takes no tree")
        return
    if tree is None:
        raise ValueError(f"metric {metric.name!r} needs the table's "
                         f"phylogenetic tree (tree=)")
    if not isinstance(tree, PhyloTree):
        raise TypeError(f"tree must be a repro.dist.PhyloTree, got "
                        f"{type(tree).__name__}")
    if tree.num_tips != width:
        raise ValueError(f"the tree places {tree.num_tips} features; the "
                         f"table has {width}")


def _metric_input(x, metric: Metric, tree):
    """What ``metric`` reads of the (n, d) float32 table ``x``: the table,
    or for a tree metric its branch embedding on ``tree``."""
    check_tree(metric, tree, x.shape[1])
    return tree_hoist(x, tree) if takes_tree(metric) else x


def pairwise_condensed(x, metric="braycurtis", *,
                       block: int = _DEFAULT_BLOCK,
                       feature_block: int = _DEFAULT_FEATURE_BLOCK,
                       tree=None) -> dict:
    """Condensed distances + fused hoists from an (n, d) feature table.

    A tree metric (``unweighted_unifrac``) needs ``tree``, the table's
    ``repro.dist.tree.PhyloTree``: production then runs on the table's
    branch embedding, made once here (``tree.tree_hoist``).

    Returns a dict:

    * ``condensed``   — (m,) scipy-pdist-layout distances, fp32;
    * ``row_means``   — (n,) row means of E = −½ D∘D (the
      ``CenteredGramOperator`` hoist, accumulated tile-by-tile);
    * ``global_mean`` — () global mean of E;
    * ``n`` / ``metric`` — provenance.

    The square n×n matrix is never allocated; peak memory is one
    (block, n) strip plus the condensed output.
    """
    metric = get_metric(metric)
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"expected an (n, d) feature table, got {x.shape}")
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    x = _metric_input(x, metric, tree)
    n = x.shape[0]
    d = int(x.shape[1])
    b = clamp_block(n, block)
    obs = current_obs()          # the ambient session (NULL_OBS when none)

    cond_parts, rs2_parts = [], []
    with obs.span("dist.pairwise_condensed", phase="production", n=n, d=d,
                  block=b, metric=metric.name,
                  reduce="product" if is_product(metric) else "elementwise",
                  panels=-(-n // b)):
        for i0 in range(0, n, b):
            i1 = min(i0 + b, n)
            xi = x[i0:i1]
            if i1 - i0 < b:                 # pad the short tail panel so
                xi = jnp.pad(xi, ((0, b - (i1 - i0)), (0, 0)))  # one trace fits all
            strip, rs2 = _run_panel(xi, x, metric, feature_block)
            rs2_parts.append(rs2[:i1 - i0])
            idx = _panel_condensed_indices(n, i0, i1)
            if idx.size:
                cond_parts.append(strip.reshape(-1)[jnp.asarray(idx)])
    obs.charge_production(n, d, b, metric=metric.name)

    rowsum_d2 = jnp.concatenate(rs2_parts)
    condensed = (jnp.concatenate(cond_parts) if cond_parts
                 else jnp.zeros((0,), dtype=x.dtype))
    row_means = -0.5 * rowsum_d2 / n
    return {"condensed": condensed, "row_means": row_means,
            "global_mean": jnp.mean(row_means), "n": n,
            "metric": metric.name}


def pairwise_distances(x, metric="braycurtis", *, out: str = "square",
                       block: int = _DEFAULT_BLOCK,
                       feature_block: int = _DEFAULT_FEATURE_BLOCK,
                       tree=None) -> jax.Array:
    """The ``scipy.spatial.distance.pdist``/``squareform`` replacement.

    ``out="square"`` assembles the full (n, n) matrix panel-by-panel,
    exactly symmetric and hollow: for an elementwise metric by
    construction (each (i, j) is the same fp expression as (j, i)); a
    product metric's (i, j) and (j, i) round apart and its diagonal
    keeps about 1e-6, so its upper triangle is mirrored over a zero
    diagonal. ``out="condensed"`` is the pdist layout via the streaming
    driver (no n×n allocated).
    """
    if out == "condensed":
        return pairwise_condensed(x, metric, block=block,
                                  feature_block=feature_block,
                                  tree=tree)["condensed"]
    if out != "square":
        raise ValueError(f"out must be 'square' or 'condensed', got {out!r}")
    metric = get_metric(metric)
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"expected an (n, d) feature table, got {x.shape}")
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    x = _metric_input(x, metric, tree)
    n = x.shape[0]
    b = clamp_block(n, block)
    parts = []
    for i0 in range(0, n, b):
        i1 = min(i0 + b, n)
        xi = x[i0:i1]
        if i1 - i0 < b:
            xi = jnp.pad(xi, ((0, b - (i1 - i0)), (0, 0)))
        strip, _ = _run_panel(xi, x, metric, feature_block)
        parts.append(strip[:i1 - i0])
    square = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    if is_product(metric):
        upper = jnp.triu(square, 1)
        square = upper + upper.T
    return square
