"""jit'd public wrapper for the tiled pairwise-distance kernel.

Handles block selection (lane-snapped per backend via ``pick_block``, the
shared rule), zero-padding of the column-block and feature axes (zero
features are identity for every registered metric's accumulators; padded
Xⱼ rows produce junk columns that are sliced off), and the backend
dispatch: ``interpret=None`` runs TPU-native on a TPU backend and falls
back to the Pallas interpreter elsewhere (this container's CPU).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.metrics import Metric
from repro.kernels.dispatch import (TPU_LANE, lane_geometry, pick_block,
                                    resolve_interpret)
from repro.kernels.pairwise import pairwise_panel
from repro.obs.compile import note_trace

_DEFAULT_BLOCK = 256
_DEFAULT_FEATURE_BLOCK = 128


@partial(jax.jit, static_argnames=("metric", "block_n", "feature_block",
                                   "interpret"))
def pairwise_panel_pallas(xi: jax.Array, x: jax.Array, *, metric: Metric,
                          block_n: int = _DEFAULT_BLOCK,
                          feature_block: int = _DEFAULT_FEATURE_BLOCK,
                          interpret: Optional[bool] = None) -> jax.Array:
    """One distance row panel via the Pallas kernel: (bm, d) × (n, d) →
    (bm, n), the metric's elementwise reduce fused in-register.

    ``xi`` is the row panel (its padding, if any, is the caller's — junk
    output *rows* are the caller's to slice); ``x`` is the full feature
    table. Column blocks and the feature axis are padded here.
    """
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    note_trace("kernels.pairwise_panel",
               (tuple(xi.shape), n, d, metric.name, block_n, feature_block,
                interpret))
    # TPU-native tiles need lane-aligned (multiple-of-128) trailing dims
    lane, floor = lane_geometry(interpret)
    if not interpret:
        # the (bn, d) Xⱼ block is double-buffered in VMEM beside the
        # metric's broadcast term: at d=2048 a 256-wide block overflows
        # the v5e's 16 MiB scoped VMEM, a 128-wide one fits
        block_n = min(block_n, TPU_LANE)
    bn = pick_block(n, block_n, lane, floor=floor)
    pad_n = (-n) % bn
    fb = min(feature_block, d)
    pad_d = (-d) % fb

    if pad_d:
        xi = jnp.pad(xi, ((0, 0), (0, pad_d)))
        x = jnp.pad(x, ((0, 0), (0, pad_d)))
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))

    out = pairwise_panel(xi, x, metric, block_n=bn, feature_block=fb,
                         interpret=interpret)
    return out[:, :n]
