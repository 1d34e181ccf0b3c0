"""Pallas kernel: fused center-matvec for matrix-free PCoA (paper §4.1).

Computes ``out = F @ X`` for the Gower-centered ``F = E − r·1ᵀ − 1·rᵀ + m``
(``E = −½ D∘D``) without ever materializing F or E:

* grid (n/bm, n/bn), the **column dimension innermost** — the (bm, k)
  output strip stays VMEM-resident across the whole j sweep (Pallas elides
  the re-fetch when the BlockSpec index is unchanged between consecutive
  steps), so each output element is written to HBM exactly once;
* per (i, j) tile: the D block is squared/halved **in-register** and fed
  straight to the MXU against the (bn, k) X block — the paper's "compute
  while the data is already in cache", applied to the E-formation;
* on the last column step the rank-1 centering corrections are applied
  in-register: ``− r_i·(1ᵀX) + (m·1ᵀX − rᵀX)``, both O(k) vectors the
  caller hoisted once.

HBM traffic: one read of D, one read of X per row strip, one write of the
(n, k) result — vs materialize-then-multiply's extra n² write + n² read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import HIGHEST


def _center_matvec_kernel(d_ref, x_ref, rm_ref, colsum_ref, corr_ref,
                          out_ref):
    """out[i] = Σ_j (−½ D_ij∘D_ij) @ X_j − r_i·colsumᵀ + corrᵀ."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = d_ref[...]
    e = -0.5 * d * d                     # E tile formed in-register
    out_ref[...] += jnp.dot(e, x_ref[...], precision=HIGHEST,
                            preferred_element_type=out_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():                       # rank-1 corrections, in-register
        out_ref[...] += corr_ref[...] - rm_ref[...] * colsum_ref[...]


def center_matvec(d: jax.Array, x: jax.Array, row_means: jax.Array,
                  colsum: jax.Array, corr: jax.Array, *, block_m: int,
                  block_n: int, interpret: bool = True) -> jax.Array:
    """Tiled ``F @ X``. All operands pre-padded to block multiples.

    d: (n, n); x: (n, k); row_means: (n,) row means of E;
    colsum: (k,) ``1ᵀX``; corr: (k,) ``m·1ᵀX − rᵀX``. The vectors enter
    the kernel as (n, 1) / (1, k) blocks: Mosaic tiles 1-D blocks to a
    layout XLA does not share, and refuses them.
    """
    n = d.shape[0]
    k = x.shape[1]
    grid = (n // block_m, n // block_n)  # j innermost → out-strip residency
    return pl.pallas_call(
        _center_matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, k), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), d.dtype),
        interpret=interpret,
    )(d, x, row_means[:, None], colsum[None, :], corr[None, :])
