"""Kernels for the paper's memory-bound hot spots.

The analysis path runs one kernel family from here, in plain XLA:

* ``permute_reduce`` — B permuted condensed multiply-reduces per tile:
                     the invariant streams once per chunk and the
                     permuted gather is closed-form triangle indexing —
                     the Mantel/ANOSIM permutation hot loop with no n²
                     buffer anywhere. ``permute_reduce_rows`` is its
                     square twin for the TPU: whole-row gathers of two
                     hollow squares, no element gather (the Mantel
                     draws' ``"rows"`` layout).

``dispatch`` holds the shared tile policy and ``HIGHEST``. The Pallas
kernels below keep the three-file convention (``<name>.py`` the
``pl.pallas_call`` body, ``<name>_ops.py`` the jit'd wrapper,
``<name>_ref.py`` the oracle); no analysis path calls them:

* ``center``       — two-pass fused PCoA centering (paper Algorithm 2).
* ``symhollow``    — fused symmetric+hollow validation (paper Algorithm 7).
* ``mantel_corr``  — batched permuted-Pearson reduction with Y-tile reuse
                     (paper Algorithm 5, square operands).
* ``rmsnorm``      — the paper's fusion discipline applied to the LM stack's
                     most common memory-bound op (3 passes → 1).

``center_matvec_ref`` is the oracle of the centred-Gram matvec
(``core.operators``).
"""

from repro.kernels.center_ops import center_distance_matrix_pallas
from repro.kernels.symhollow_ops import is_symmetric_and_hollow_pallas
from repro.kernels.mantel_corr_ops import mantel_corr_pallas
from repro.kernels.permute_reduce_ops import (permute_reduce,
                                              permute_reduce_rows)
from repro.kernels.rmsnorm_ops import rmsnorm_pallas

__all__ = [
    "center_distance_matrix_pallas",
    "is_symmetric_and_hollow_pallas",
    "mantel_corr_pallas",
    "permute_reduce",
    "permute_reduce_rows",
    "rmsnorm_pallas",
]
