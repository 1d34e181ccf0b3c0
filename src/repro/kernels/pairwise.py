"""Pallas kernel: tiled pairwise-distance row panel (repro.dist hot loop).

The O(n²·d) step upstream of every analysis in this repo is building the
distance matrix itself — "Enabling microbiome research on personal
devices" (Sfiligoi et al. 2021) shows it dominating real workflows, and
it is the same memory-access story as the paper's §4 kernels: a naive
NumPy composition materializes (n, n, d) broadcast intermediates (or
re-streams X from DRAM once per output row), where the tiled form reads
each X block into fast memory once per tile pair and fuses the metric's
elementwise reduce in-register.

This kernel produces ONE row panel ``out[i0:i0+bm, :]`` so the driver can
stream panels straight into the condensed form and the fused hoist
accumulators without a square n×n ever existing:

* grid ``(n/bn,)`` over column blocks; the Xᵢ panel (bm, d) has a
  constant BlockSpec index, so Pallas keeps it VMEM-resident across the
  whole j sweep (the re-fetch is elided when the index is unchanged);
* per step the (bn, d) Xⱼ block is fetched once and the metric's
  accumulators are built chunk-by-chunk over the feature axis — the
  (bm, bn, dc) broadcast term lives only in registers/VMEM for one chunk,
  never in HBM;
* ``metric.finish`` runs on the summed accumulators while the tile is
  still resident, writing the finished (bm, bn) distance tile exactly
  once.

HBM traffic per panel: bm·d (Xᵢ, once) + n·d (Xⱼ blocks) + bm·n (the
output) — vs the broadcast form's bm·n·d intermediate write+read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.dist.metrics import Metric, merge_acc

#: rows per grid step. The metric's (rows, bn, feature_block) broadcast
#: term is the kernel's largest value: 512 KiB at 8 x 128 x 128, where a
#: whole 256-row panel's 32 MiB did not finish compiling for the v5e.
_ROWS = 8


def _pairwise_kernel(metric: Metric, feature_block: int,
                     xi_ref, xj_ref, out_ref):
    xi = xi_ref[...]                     # (bm, d) — resident across j
    xj = xj_ref[...]                     # (bn, d) — this column block
    d = xi.shape[-1]
    acc = None
    for c0 in range(0, d, feature_block):      # static chunk loop: the
        a = xi[:, c0:c0 + feature_block]       # (bm, bn, dc) broadcast
        b = xj[:, c0:c0 + feature_block]       # term never leaves VMEM
        part = metric.accumulate(a, b)
        acc = part if acc is None else merge_acc(acc, part)
    out_ref[...] = metric.finish(acc).astype(out_ref.dtype)


def pairwise_panel(xi: jax.Array, xj: jax.Array, metric: Metric, *,
                   block_n: int, feature_block: int,
                   interpret: bool = True) -> jax.Array:
    """Distance row panel ``d(xi, xj)``: (bm, d) × (n, d) → (bm, n).

    All operands pre-padded by the caller: ``xj`` rows to a ``block_n``
    multiple, features of both to a ``feature_block`` multiple (zero
    features are identity for every metric's accumulators — see
    ``repro.dist.metrics``). ``metric`` must be hashable (the frozen
    dataclass instances are); the kernel specializes per metric.
    """
    bm, d = xi.shape
    n = xj.shape[0]
    rb = _ROWS if bm % _ROWS == 0 else bm
    # column block outer, row sub-panel inner: the (bn, d) Xⱼ block stays
    # resident while the panel's rows stream past it
    grid = (n // block_n, bm // rb)
    kernel = lambda a, b, o: _pairwise_kernel(metric, feature_block,
                                              a, b, o)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((rb, block_n), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bm, n), xi.dtype),
        interpret=interpret,
    )(xi, xj)
