"""Pallas kernel: batched permuted-Pearson reduction (paper §4.2, Algorithm 5).

TPU-native formulation of the paper's fused Mantel inner loop (DESIGN §2):

* the permutation-invariant statistics (x̄, ‖x−x̄‖, ŷ) are hoisted by the
  caller — the paper's two big wins;
* the permutation *gathers* run in XLA (contiguous row gathers — the same
  locality argument the paper's Cython loop makes for its row-resident
  access); scalar random access does not vectorize on the VPU;
* this kernel fuses everything downstream: the multiply-reduce of a *batch*
  of B permuted matrices against the shared Ŷ, tiled so each Ŷ tile is
  fetched into VMEM **once per (i,j) and reused across all B permutations**
  (the b grid dimension is innermost; Pallas elides the re-fetch when the
  BlockSpec index is unchanged between consecutive steps). HBM traffic per
  permutation: n² (its own Xp) + n²/B (its share of Ŷ) — vs the original's
  ~5·n² per permutation (fresh mean/norm/divide/dot passes).

The mean-subtraction drops out entirely: Σŷ = 0 ⇒
``r_p = ⟨x_p, ŷ⟩ / ‖x−x̄‖`` (DESIGN §3.2), so the kernel is a pure
fused multiply-accumulate — ideal VPU work.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret


def _mantel_kernel(xp_ref, y_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xp = xp_ref[...]          # (1, bm, bn) — this permutation's tile
    y = y_ref[...]            # (bm, bn)    — shared, VMEM-resident across b
    out_ref[...] += jnp.sum(xp[0] * y)[None]


def mantel_corr(xp: jax.Array, yhat: jax.Array, *, block_m: int,
                block_n: int, interpret: Optional[bool] = None) -> jax.Array:
    """stats[b] = Σ_ij xp[b,i,j]·yhat[i,j]; caller divides by 2‖x−x̄‖.

    xp: (B, n, n) batch of row+col permuted X. yhat: (n, n) symmetric
    centered-normalized Y with zero diagonal. ``interpret=None`` resolves
    by backend: native Mosaic lowering on a TPU, the Pallas interpreter
    everywhere else (this container's CPU).
    """
    interpret = resolve_interpret(interpret)
    b_perms, n, _ = xp.shape
    grid = (n // block_m, n // block_n, b_perms)   # b innermost → Y-tile reuse
    return pl.pallas_call(
        _mantel_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_n), lambda i, j, b: (b, i, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j, b: (i, j)),
        ],
        out_specs=pl.BlockSpec((1,), lambda i, j, b: (b,)),
        out_shape=jax.ShapeDtypeStruct((b_perms,), xp.dtype),
        interpret=interpret,
    )(xp, yhat)
