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
m = n(n−1)/2 — the square matrix is never allocated. Panel compute
dispatches per ``impl``: ``"pallas"`` routes through the VMEM-tiled
``kernels.pairwise`` (backend-dispatched interpret, like ``mantel_corr``),
``"xla"`` is the ``lax.map`` row-panel fallback — sub-panels of rows
stream against the full table with the metric's reduce feature-chunked,
so the broadcast term stays (rows, n, chunk)-bounded.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.metrics import Metric, get_metric, merge_acc
from repro.kernels.dispatch import clamp_block
from repro.obs.compile import note_trace
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


@partial(jax.jit, static_argnames=("metric", "feature_block", "impl",
                                   "interpret", "block"))
def _panel_stats(xi: jax.Array, x: jax.Array, *, metric: Metric,
                 feature_block: int, impl: str, interpret: Optional[bool],
                 block: int):
    """One row strip + its fused row sums: (strip, Σ_j d²).

    The row sums ride the same jit region as the strip compute, so XLA
    fuses them into the panel sweep — the hoist costs no extra pass."""
    note_trace("dist.panel_stats",
               (xi.shape, x.shape, metric.name, feature_block, impl, block))
    if impl == "pallas":
        from repro.kernels.pairwise_ops import pairwise_panel_pallas
        strip = pairwise_panel_pallas(xi, x, metric=metric, block_n=block,
                                      feature_block=feature_block,
                                      interpret=interpret)
    else:
        strip = _panel_xla(xi, x, metric, feature_block)
    return strip, jnp.sum(strip * strip, axis=1)


def pairwise_condensed(x, metric="braycurtis", *,
                       block: int = _DEFAULT_BLOCK,
                       feature_block: int = _DEFAULT_FEATURE_BLOCK,
                       impl: str = "xla",
                       interpret: Optional[bool] = None) -> dict:
    """Condensed distances + fused hoists from an (n, d) feature table.

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
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown pairwise impl {impl!r}")
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"expected an (n, d) feature table, got {x.shape}")
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    n = x.shape[0]
    d = int(x.shape[1])
    b = clamp_block(n, block)
    obs = current_obs()          # the ambient session (NULL_OBS when none)

    cond_parts, rs2_parts = [], []
    with obs.span("dist.pairwise_condensed", phase="production", n=n, d=d,
                  block=b, impl=impl, metric=metric.name,
                  panels=-(-n // b)):
        for i0 in range(0, n, b):
            i1 = min(i0 + b, n)
            xi = x[i0:i1]
            if i1 - i0 < b:                 # pad the short tail panel so
                xi = jnp.pad(xi, ((0, b - (i1 - i0)), (0, 0)))  # one trace fits all
            strip, rs2 = _panel_stats(xi, x, metric=metric,
                                           feature_block=feature_block,
                                           impl=impl, interpret=interpret,
                                           block=b)
            rs2_parts.append(rs2[:i1 - i0])
            idx = _panel_condensed_indices(n, i0, i1)
            if idx.size:
                cond_parts.append(strip.reshape(-1)[jnp.asarray(idx)])
    obs.charge_production(n, d, b, metric=metric.name, impl=impl)

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
                       impl: str = "xla",
                       interpret: Optional[bool] = None) -> jax.Array:
    """The ``scipy.spatial.distance.pdist``/``squareform`` replacement.

    ``out="square"`` assembles the full (n, n) matrix panel-by-panel
    (exactly symmetric and hollow by construction — each (i, j) is the
    same fp expression as (j, i)); ``out="condensed"`` is the pdist
    layout via the streaming driver (no n×n allocated).
    """
    if out == "condensed":
        return pairwise_condensed(x, metric, block=block,
                                  feature_block=feature_block, impl=impl,
                                  interpret=interpret)["condensed"]
    if out != "square":
        raise ValueError(f"out must be 'square' or 'condensed', got {out!r}")
    metric = get_metric(metric)
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown pairwise impl {impl!r}")
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"expected an (n, d) feature table, got {x.shape}")
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    n = x.shape[0]
    b = clamp_block(n, block)
    parts = []
    for i0 in range(0, n, b):
        i1 = min(i0 + b, n)
        xi = x[i0:i1]
        if i1 - i0 < b:
            xi = jnp.pad(xi, ((0, b - (i1 - i0)), (0, 0)))
        strip, _ = _panel_stats(xi, x, metric=metric,
                                   feature_block=feature_block, impl=impl,
                                   interpret=interpret, block=b)
        parts.append(strip[:i1 - i0])
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
