"""Per-kernel analytic cost model: traffic AND residency as closed forms.

The traffic side is NOT a re-derivation — every floats-moved figure here
is computed by calling the audited ``repro.obs.ledger`` registry
(``HOIST_PASSES``/``FEATURE_HOIST_PASSES``, ``perm_traffic_floats``,
``production_floats``) so the tuner's model and the runtime's ledger
charges are the same functions and can never drift. What this module
*adds* is the **resident-set** side: for each kernel, the fp32 working
set that must stay cache/VMEM-resident as a closed form of the tile
knobs — the quantity the ``repro.tune.solve`` solver fits against the
measured ``BackendBudget``. The snapping rules are the shared
``kernels.dispatch`` helpers, so modeled tiles equal executed tiles.

Parameter names match the ledger's: n observations, d features, K
permutations, B permutation batch, S streamed invariant rows
(Mantel/ANOSIM 1, partial Mantel 2), plus the tile knobs block /
feature_block / chunk.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.kernels.dispatch import clamp_block, pick_block, snap_chunk
from repro.obs.ledger import (FEATURE_HOIST_PASSES, HOIST_PASSES,
                              hoist_floats, perm_traffic_floats,
                              production_floats)

__all__ = [
    "CostTerms", "condensed_size", "perm_batch_cost", "perm_batch_fit",
    "production_cost", "matvec_cost", "session_hoist_passes",
    "SQUARE_SESSION_ARTIFACTS", "STANDALONE_SESSION_ARTIFACTS",
]

#: artifact builds of the canonical 4-analysis battery (pcoa + permanova
#: + permdisp + anosim) on ONE shared Workspace — the BENCH_api
#: "11 passes" side of the published 11-vs-16 accounting
SQUARE_SESSION_ARTIFACTS = ("operator", "gram", "condensed", "ranks",
                            "coords")
#: the same battery as four one-shot Workspaces (the legacy free
#: functions) — the "16 passes" side: pcoa and permdisp each rebuild
#: operator+coords, permanova rebuilds gram, anosim condensed+ranks
STANDALONE_SESSION_ARTIFACTS = ("operator", "coords",      # pcoa
                                "gram",                    # permanova
                                "operator", "coords",      # permdisp
                                "condensed", "ranks")      # anosim


def condensed_size(n: int) -> int:
    """m = n(n−1)/2 (duplicated from ``dist.driver`` to keep this module
    import-light; the parity test pins them equal)."""
    return n * (n - 1) // 2


@dataclasses.dataclass(frozen=True)
class CostTerms:
    """One kernel configuration, costed.

    * ``traffic_floats``  — fp32 floats streamed end to end (the ledger
      figure; what the solver minimizes);
    * ``resident_floats`` — fp32 floats that must be simultaneously
      live for the tile loop to achieve the modeled traffic (what the
      solver fits under the budget);
    * ``base_floats``     — untunable always-resident state (e.g. the
      condensed source xc of the permutation loop): reported so budget
      audits see the full footprint, but EXCLUDED from the tunable fit —
      no tile choice can shrink it, and at production n it exceeds any
      L2-class budget on its own;
    * ``params``          — the parameter point, for RunReport audits.
    """

    op: str
    traffic_floats: float
    resident_floats: float
    base_floats: float
    params: dict

    @property
    def traffic_bytes(self) -> float:
        return 4.0 * self.traffic_floats

    @property
    def resident_bytes(self) -> float:
        return 4.0 * self.resident_floats

    def to_dict(self) -> dict:
        return {"op": self.op, "traffic_floats": self.traffic_floats,
                "traffic_bytes": self.traffic_bytes,
                "resident_floats": self.resident_floats,
                "resident_bytes": self.resident_bytes,
                "base_floats": self.base_floats,
                "params": dict(self.params)}


# --------------------------------------------------------------------------
# the permutation inner loop (kernels.permute_reduce)
# --------------------------------------------------------------------------
def perm_resident_floats(n: int, batch: int, chunk: int, s: int = 1
                         ) -> float:
    """Tunable working set of one ``permute_reduce`` scan step: the
    (B, chunk) gather tile, the (S, chunk) invariant tile, the two
    (chunk,) triangle-coordinate rows, and the (B, n) order block that
    every step re-reads. (The (m,) condensed source is ``base``, not
    counted here — see ``CostTerms.base_floats``.)"""
    return float(chunk) * (batch + s + 2) + float(batch) * n


def perm_batch_fit(n: int, chunk: int, budget_floats: float, s: int = 1
                   ) -> int:
    """Largest batch B whose tunable working set fits ``budget_floats``
    at the given chunk — the reuse clamp of the effective-traffic model:
    past this B the ŷ/ii/jj tiles no longer stay resident across the
    batch, so the modeled 3m/B amortization stops improving."""
    # chunk·(B+s+2) + B·n <= budget  ⇒  B <= (budget − chunk(s+2)) / (chunk+n)
    b = int((budget_floats - float(chunk) * (s + 2)) // (chunk + n))
    return max(b, 1)


def perm_batch_cost(n: int, batch: int, chunk: int, s: int = 1,
                    budget_floats: Optional[float] = None) -> CostTerms:
    """Per-permutation cost of the condensed fused loop at (B, chunk).

    Traffic is the ledger's ``condensed_fused`` term — m(1 + 3/B) + n
    per permutation — evaluated at the EFFECTIVE batch
    ``min(B, perm_batch_fit(...))`` when a budget is supplied: a batch
    too large for its invariant tiles to stay resident gets no reuse
    credit beyond the batch that does fit.
    """
    m = condensed_size(n)
    chunk, _ = snap_chunk(m, chunk)
    b_eff = batch
    if budget_floats is not None:
        b_eff = min(batch, perm_batch_fit(n, chunk, budget_floats, s))
    per_perm = perm_traffic_floats(n, max(b_eff, 1))["condensed_fused"]
    return CostTerms(
        op="perm_batch", traffic_floats=per_perm,
        resident_floats=perm_resident_floats(n, batch, chunk, s),
        base_floats=float(m),
        params={"n": n, "batch": batch, "batch_effective": b_eff,
                "chunk": chunk, "s": s, "model": "condensed_fused"})


# --------------------------------------------------------------------------
# the tiled distance production sweep (dist.driver)
# --------------------------------------------------------------------------
def production_cost(n: int, d: int, block: int,
                    feature_block: int = 128) -> CostTerms:
    """Feature traffic and residency of the tiled pairwise production.

    Traffic is the ledger's ``production_floats`` (⌈n/b⌉·n·d + n·d —
    the clamp inside it is ``dispatch.clamp_block``'s rule). Residency
    per panel step: the (b, d) row panel, one (b, feature_block)
    column-block operand pair, and the (b, n) output strip.
    """
    b = clamp_block(n, block)
    fb = max(min(feature_block, d), 1)
    resident = float(b) * d + 2.0 * b * fb + float(b) * n
    return CostTerms(
        op="production", traffic_floats=production_floats(n, d, block),
        resident_floats=resident, base_floats=0.0,
        params={"n": n, "d": d, "block": b, "feature_block": fb})


# --------------------------------------------------------------------------
# the centered-operator matvec (core.operators) / fsvd coords
# --------------------------------------------------------------------------
def matvec_cost(n: int, k: int, block: int, passes: float = 1.0,
                lane: int = 8) -> CostTerms:
    """Traffic and residency of ``passes`` fused center-matvec sweeps
    (the coords artifact is ``passes=HOIST_PASSES['coords']`` = 4 such
    reads of D). Traffic per pass is one read of D — n² floats, the
    ledger's ``hoist_floats`` unit. Residency per tile step: one
    (b, b) D tile, the (b, k) x panel, and the (b, k) partial output.
    """
    b = pick_block(n, block, lane)
    resident = float(b) * b + 2.0 * float(b) * max(k, 1)
    return CostTerms(
        op="matvec", traffic_floats=passes * hoist_floats("square", n),
        resident_floats=resident, base_floats=0.0,
        params={"n": n, "k": k, "block": b, "passes": passes})


# --------------------------------------------------------------------------
# session-level pass accounting (the BENCH_api 11-vs-16 battery)
# --------------------------------------------------------------------------
def session_hoist_passes(artifacts, feature_backed: bool = False) -> float:
    """Total n²-passes of a session that builds ``artifacts`` (in
    order, duplicates = rebuilds), straight from the ledger's pass
    tables. ``session_hoist_passes(SQUARE_SESSION_ARTIFACTS)`` is the
    published 11; ``...(STANDALONE_SESSION_ARTIFACTS)`` the 16."""
    t = FEATURE_HOIST_PASSES if feature_backed else HOIST_PASSES
    return float(sum(t.get(a, 0.0) for a in artifacts))
