"""Beta-diversity distance metrics as pytree dataclasses.

Every metric this subsystem ships reduces a pair of feature vectors to a
distance through the same algebraic shape: a sum over features of a
per-feature term (one or two running accumulators; for unweighted
UniFrac a matrix product, ``product``), followed by a cheap finishing
transform. That shape is exactly what the tiled pairwise driver
and the Pallas kernel need — the per-feature terms can be accumulated
chunk-by-chunk while the (bm, d) × (bn, d) tiles are resident in
VMEM/cache, and only the tiny (bm, bn) accumulators survive between
chunks.

A ``Metric`` therefore declares two hooks (the same design language as
``stats.engine.Statistic``'s hoist/per_perm split):

* ``accumulate(xi, xj)`` — partial accumulators for ONE feature chunk:
  ``xi`` (bm, dc) against ``xj`` (bn, dc) → dict of (bm, bn) arrays.
  Accumulators are additive over feature chunks (the driver simply sums
  dicts), which is what lets the reduce fuse into the tile sweep.
* ``finish(acc)`` — the (bm, bn) distance tile from the summed
  accumulators.

Instances are frozen ``register_dataclass`` pytrees with no data fields,
so they are hashable (usable as ``jax.jit`` static arguments — the kernel
specializes per metric) and can also ride inside jitted pytrees.

Zero-feature padding is free for every metric: a feature where both
vectors are 0 contributes 0 to every accumulator (for Jaccard the
"either nonzero" count is 0 too), so the driver pads the feature axis to
chunk multiples without masking.

Degenerate-pair conventions (pinned by ``tests/test_dist.py``):

* **Bray–Curtis 0/0** — two all-zero samples have denominator 0; we
  define d = 0 (identical samples), where SciPy ≥ 1.9 returns NaN. This
  is the scikit-bio/QIIME convention: an empty sample is identical to
  another empty sample, not incomparably far from it.
* **Jaccard 0/0** — d = 0, matching SciPy's own convention since 1.2.
* **Canberra 0/0 terms** — per-feature 0/0 terms count as 0 (SciPy's
  convention).

The five feature metrics match ``scipy.spatial.distance.pdist`` to
≤ 1e-5 on random fp32 tables (property-tested), modulo the Bray–Curtis
NaN convention above. Unweighted UniFrac, the one tree metric, matches
the float64 reference ``dist.unifrac_ref`` instead; scipy has none.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

Acc = Dict[str, jax.Array]


@runtime_checkable
class Metric(Protocol):
    """A pairwise distance metric, split at the chunk-accumulation boundary.

    ``name`` is the registry key (and what ``ExecConfig.metric`` /
    ``Workspace.from_features(metric=...)`` accept); ``accumulate`` maps
    one feature chunk of both tiles to additive (bm, bn) accumulators;
    ``finish`` turns the summed accumulators into the distance tile.
    """

    name: str

    def accumulate(self, xi: jax.Array, xj: jax.Array) -> Acc: ...

    def finish(self, acc: Acc) -> jax.Array: ...


def _pairwise(xi: jax.Array, xj: jax.Array):
    """Broadcast one feature chunk to per-pair terms: (bm, bn, dc)."""
    return xi[:, None, :], xj[None, :, :]


def _safe_div(num: jax.Array, den: jax.Array) -> jax.Array:
    """num/den with the 0/0 → 0 convention (identical/empty samples)."""
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Euclidean:
    """√Σ(a−b)² — computed diff-based (not the ‖a‖²+‖b‖²−2a·b Gram trick,
    which loses ~3 decimal digits to cancellation in fp32) so the pdist
    oracle parity holds at 1e-5."""

    name = "euclidean"

    def accumulate(self, xi, xj):
        a, b = _pairwise(xi, xj)
        d = a - b
        return {"ss": jnp.sum(d * d, axis=-1)}

    def finish(self, acc):
        return jnp.sqrt(jnp.maximum(acc["ss"], 0.0))


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Cityblock:
    """Σ|a−b| (Manhattan)."""

    name = "cityblock"

    def accumulate(self, xi, xj):
        a, b = _pairwise(xi, xj)
        return {"s": jnp.sum(jnp.abs(a - b), axis=-1)}

    def finish(self, acc):
        return acc["s"]


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Canberra:
    """Σ |a−b| / (|a|+|b|), 0/0 feature terms counting 0 (SciPy)."""

    name = "canberra"

    def accumulate(self, xi, xj):
        a, b = _pairwise(xi, xj)
        den = jnp.abs(a) + jnp.abs(b)
        return {"s": jnp.sum(_safe_div(jnp.abs(a - b), den), axis=-1)}

    def finish(self, acc):
        return acc["s"]


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class BrayCurtis:
    """Σ|a−b| / Σ|a+b| — THE workhorse of microbiome beta diversity
    (Sfiligoi et al. 2021). 0/0 (two empty samples) → 0, documented
    above; intended for non-negative abundance tables."""

    name = "braycurtis"

    def accumulate(self, xi, xj):
        a, b = _pairwise(xi, xj)
        return {"num": jnp.sum(jnp.abs(a - b), axis=-1),
                "den": jnp.sum(jnp.abs(a + b), axis=-1)}

    def finish(self, acc):
        return _safe_div(acc["num"], acc["den"])


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Jaccard:
    """Presence/absence disagreement: #(a≠b) / #(a≠0 ∨ b≠0), SciPy's
    real-vector semantics (a≠b implies at least one is nonzero, so the
    numerator needs no nonzero guard). 0/0 → 0 like SciPy ≥ 1.2."""

    name = "jaccard"

    def accumulate(self, xi, xj):
        a, b = _pairwise(xi, xj)
        dt = xi.dtype
        return {"neq": jnp.sum((a != b).astype(dt), axis=-1),
                "nz": jnp.sum(((a != 0) | (b != 0)).astype(dt), axis=-1)}

    def finish(self, acc):
        return _safe_div(acc["neq"], acc["nz"])


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class UnweightedUniFrac:
    """Unweighted UniFrac (Lozupone & Knight 2005): the branch length
    present in exactly one of two samples over the branch length present
    in either. It reads the branch embedding E[x, b] = l_b·[b present in
    x] that ``tree.tree_hoist`` makes from a table and its tree
    (``takes_tree``). E = I·diag(l) with I exactly 0/1, so the metric is
    bilinear: with the shared length S = [E > 0]·Eᵀ and the row totals
    L = Σ_b E, the unshared length is L_x + L_y − 2S and the length in
    either L_x + L_y − S. ``accumulate`` returns the chunk's shared
    length and the pair's total, both additive over feature chunks, from
    one matrix product (``product``: production runs a panel as one
    product over the whole width): the presence of ``xi``'s rows, and a
    row of ones, against ``xj``'s embedding, so the ones row gives
    ``xj``'s row totals without a second read of it. The 0/1 side is
    bfloat16, in which it is exact; the lengths are not, so the product
    runs at ``Precision.HIGHEST``, where the default precision would
    round them to bfloat16. ``finish`` clamps the unshared length at 0,
    which it is in exact arithmetic: rounding leaves identical samples a
    few ulps either side. 0/0 (no branch present in either sample) → 0."""

    name = "unweighted_unifrac"
    takes_tree = True
    product = True

    def accumulate(self, xi, xj):
        with jax.named_scope("dist.unifrac"):
            ones = jnp.ones((1, xi.shape[1]), bool)
            lhs = jnp.concatenate([xi > 0, ones]).astype(jnp.bfloat16)
            out = jax.lax.dot_general(
                lhs, xj, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            total = jnp.sum(xi, axis=1)[:, None] + out[-1][None, :]
            return {"shared": out[:-1], "total": total}

    def finish(self, acc):
        shared, total = acc["shared"], acc["total"]
        return _safe_div(jnp.maximum(total - 2.0 * shared, 0.0),
                         total - shared)


def takes_tree(metric: Metric) -> bool:
    """Whether ``metric`` reads a table's tree embedding, not the table."""
    return bool(getattr(metric, "takes_tree", False))


def is_product(metric: Metric) -> bool:
    """Whether ``metric``'s accumulate is one matrix product, which
    production runs over a panel's whole feature axis at once."""
    return bool(getattr(metric, "product", False))


def merge_acc(acc: Acc, part: Acc) -> Acc:
    """Sum two chunks' accumulators (all metrics are feature-additive)."""
    return {k: acc[k] + part[k] for k in acc}


METRICS: Dict[str, Metric] = {
    m.name: m for m in (Euclidean(), Cityblock(), Canberra(), BrayCurtis(),
                        Jaccard(), UnweightedUniFrac())
}


def get_metric(metric) -> Metric:
    """Coerce a metric name or instance to the registered ``Metric``."""
    if isinstance(metric, str):
        try:
            return METRICS[metric]
        except KeyError:
            raise ValueError(
                f"unknown metric {metric!r}; available: "
                f"{sorted(METRICS)}") from None
    if isinstance(metric, Metric):
        return metric
    raise TypeError(f"metric must be a name or Metric instance, "
                    f"got {type(metric).__name__}")
