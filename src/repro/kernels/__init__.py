"""Pallas TPU kernels for the paper's memory-bound hot spots.

Each kernel family has three files (harness convention):

* ``<name>.py``      — the ``pl.pallas_call`` kernel with explicit BlockSpec
                       VMEM tiling (TPU is the *target*; validated on CPU
                       with ``interpret=True``);
* ``<name>_ops.py``  — the jit'd public wrapper (padding, dtype handling,
                       block-size selection);
* ``<name>_ref.py``  — the pure-jnp oracle used by the allclose tests.

Kernels (paper hot spots only — DESIGN §3):

* ``center``       — two-pass fused PCoA centering (paper Algorithm 2).
* ``center_matvec``— fused center-matvec for matrix-free PCoA: E-formation
                     and the rank-1 centering corrections applied
                     in-register against a skinny (n, k) block.
* ``symhollow``    — fused symmetric+hollow validation (paper Algorithm 7).
* ``mantel_corr``  — batched permuted-Pearson reduction with Y-tile reuse
                     (paper Algorithm 5, TPU-native formulation; square
                     operands — kept as the materialized baseline).
* ``permute_reduce`` — the square-free successor: B permuted condensed
                     multiply-reduces per tile, the invariant streams
                     through VMEM once per chunk and the permuted gather
                     is closed-form triangle indexing — the Mantel/ANOSIM
                     permutation hot loop with no n² buffer anywhere.
                     ``permute_reduce_rows`` is its square twin for the
                     TPU: whole-row gathers of two hollow squares, no
                     element gather (the Mantel draws' ``"rows"`` layout).
* ``pairwise``     — tiled pairwise-distance row panel: the ``repro.dist``
                     metric reduce fused in-register against VMEM-resident
                     Xᵢ/Xⱼ feature blocks.
* ``rmsnorm``      — the paper's fusion discipline applied to the LM stack's
                     most common memory-bound op (3 passes → 1).
"""

from repro.kernels.center_ops import center_distance_matrix_pallas
from repro.kernels.center_matvec_ops import center_matvec_pallas
from repro.kernels.symhollow_ops import is_symmetric_and_hollow_pallas
from repro.kernels.mantel_corr_ops import mantel_corr_pallas
from repro.kernels.pairwise_ops import pairwise_panel_pallas
from repro.kernels.permute_reduce_ops import (permute_reduce,
                                              permute_reduce_rows)
from repro.kernels.rmsnorm_ops import rmsnorm_pallas

__all__ = [
    "center_distance_matrix_pallas",
    "center_matvec_pallas",
    "is_symmetric_and_hollow_pallas",
    "mantel_corr_pallas",
    "pairwise_panel_pallas",
    "permute_reduce",
    "permute_reduce_rows",
    "rmsnorm_pallas",
]
