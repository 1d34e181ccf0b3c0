"""Pure-jnp oracle for distance production: naive full-broadcast pairwise
distances, each metric written out longhand the way scipy.spatial.distance
documents it (the memory behaviour the tiled ``driver`` exists to avoid —
the (n, m, d) broadcast intermediate is materialized whole)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _guarded(num: jax.Array, den: jax.Array) -> jax.Array:
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def pairwise_ref(x: jax.Array, y: jax.Array, metric: str) -> jax.Array:
    """Distance matrix d(x_i, y_j): (n, d) × (m, d) → (n, m), eager
    broadcast formulas (0/0 conventions as pinned in repro.dist.metrics)."""
    a = x[:, None, :]
    b = y[None, :, :]
    if metric == "euclidean":
        return jnp.sqrt(jnp.maximum(jnp.sum((a - b) ** 2, -1), 0.0))
    if metric == "cityblock":
        return jnp.sum(jnp.abs(a - b), -1)
    if metric == "canberra":
        return jnp.sum(_guarded(jnp.abs(a - b), jnp.abs(a) + jnp.abs(b)), -1)
    if metric == "braycurtis":
        return _guarded(jnp.sum(jnp.abs(a - b), -1),
                        jnp.sum(jnp.abs(a + b), -1))
    if metric == "jaccard":
        dt = x.dtype
        return _guarded(jnp.sum((a != b).astype(dt), -1),
                        jnp.sum(((a != 0) | (b != 0)).astype(dt), -1))
    if metric == "unweighted_unifrac":      # on the branch embedding
        return _guarded(jnp.sum(jnp.abs(a - b), -1),
                        jnp.sum(jnp.maximum(a, b), -1))
    raise ValueError(f"unknown metric {metric!r}")
