"""The tile solver: budget in, every knob out.

Replaces the four independently hand-picked constants (center-matvec
512, mantel/pairwise/driver 256, feature_block 128, permute-reduce
chunk 64k, engine batch 8/32) with ONE policy: enumerate lane-snapped
candidates through the SAME ``kernels.dispatch`` snapping the kernels
execute, keep those whose ``repro.tune.model`` resident set fits the
``BackendBudget``, and take the one minimizing modeled *effective*
traffic (traffic evaluated at the budget-clamped reuse — a tile too big
to stay resident gets no credit for the reuse it cannot realize).

Guarantees the tests pin:

* the hand-picked default is always in the candidate set, so the
  solved choice never models worse effective traffic than the
  constants it replaces (the BENCH_tune gate);
* ``batch_size``/``chunk`` are solved from (n, S, budget) only — K is
  deliberately NOT an input, so the engine's one padded per-batch
  program keeps serving every K (the PR-5 sentinel invariant);
* ``feature_block`` AND ``block`` only ever *shrink* under budget
  pressure, never grow — growing feature_block would reorder the
  metric accumulator merges, and any block change re-associates the
  operator matvec's row-panel partial sums, moving results in the last
  ulp (the bitwise-stability rule: auto keeps the default geometry
  whenever it fits, so it stays bitwise-identical to the default run
  on any problem the default's resident set can host);
* n beyond the int32 triangle bound is refused here, before any kernel
  sees it (same guard, same message family as ``permute_reduce``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.distance_matrix import MAX_TRIANGLE_N
from repro.kernels.dispatch import lane_geometry, pick_block, snap_chunk
from repro.tune.budget import BackendBudget, detect_budget, load_profile
from repro.tune.model import (condensed_size, matvec_cost, perm_batch_cost,
                              perm_batch_fit, production_cost)

__all__ = ["TunedTiles", "solve_tiles", "resolve_exec_config",
           "DEFAULT_BLOCK", "DEFAULT_FEATURE_BLOCK", "DEFAULT_BATCH",
           "DEFAULT_CHUNK", "BATCH_MAX"]

# the hand-picked constants the solver must never price worse than —
# one authoritative copy each, asserted against the owning modules in
# tests so they cannot drift silently
DEFAULT_BLOCK = 256          # mantel_corr/pairwise/driver block
DEFAULT_FEATURE_BLOCK = 128  # pairwise/driver feature chunk
DEFAULT_BATCH = 32           # the Workspace battery's batch
DEFAULT_CHUNK = 65536        # permute_reduce condensed chunk

#: solved batches cap here regardless of budget headroom: past ~128 the
#: modeled 3m/B amortization is already <3% from its asymptote while
#: the (B, n) order block and (B, chunk) gather tile keep growing
BATCH_MAX = 128

_BLOCK_CANDIDATES = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
_CHUNK_CANDIDATES = (131072, 65536, 32768, 16384, 8192, 4096)
_BATCH_CANDIDATES = (128, 64, 32, 16, 8)
_MIN_CHUNK = 4096
_MIN_FEATURE_BLOCK = 8


@dataclasses.dataclass(frozen=True)
class TunedTiles:
    """One solved configuration: the knobs, the budget they were fit
    against, and the modeled costs of both the solved and the default
    tiles (so reports and the BENCH gate can show the delta without
    re-running the solver)."""

    n: int
    d: Optional[int]
    block: int
    feature_block: int
    batch_size: int
    chunk: int
    backend: str
    budget: BackendBudget
    modeled: dict
    modeled_default: dict

    def to_dict(self) -> dict:
        return {"n": self.n, "d": self.d, "block": self.block,
                "feature_block": self.feature_block,
                "batch_size": self.batch_size, "chunk": self.chunk,
                "backend": self.backend, "budget": self.budget.to_dict(),
                "modeled": dict(self.modeled),
                "modeled_default": dict(self.modeled_default)}


def _fit_block(n: int, d: Optional[int], fb: int, lane: int, floor: int,
               budget_floats: float, cap: Optional[int] = None) -> int:
    """Largest lane-snapped candidate block (<= ``cap`` when given)
    whose production AND matvec resident sets fit; modeled production
    traffic is non-increasing in block, so largest-that-fits is also
    cheapest-that-fits. With ``cap`` this doubles as the EFFECTIVE
    block of a requested size under the budget — what a hand-picked
    constant really achieves, which is how ``modeled_default`` is
    priced (same clamp for both sides of the comparison)."""
    d_eff = d if d is not None else 0     # dm-backed: no production sweep
    cands = set(_BLOCK_CANDIDATES + (DEFAULT_BLOCK,))
    if cap is not None:
        cands.add(cap)                    # the requested size is always
    seen = []                             # its own first candidate
    for cand in sorted(cands, reverse=True):
        if cap is not None and cand > cap:
            continue
        b = pick_block(n, cand, lane, floor=floor)
        if b in seen:
            continue
        seen.append(b)
        fits_mv = matvec_cost(n, 16, b, lane=lane).resident_floats \
            <= budget_floats
        fits_prod = (d_eff == 0
                     or production_cost(n, d_eff, b, fb).resident_floats
                     <= budget_floats)
        if fits_mv and fits_prod:
            return b
    return seen[-1] if seen else pick_block(n, floor, lane, floor=floor)


def _solve_batch_chunk(n: int, s: int, budget_floats: float
                       ) -> tuple[int, int]:
    """Joint (batch, chunk): the largest candidate batch for which some
    chunk >= _MIN_CHUNK keeps the scan step resident, paired with the
    largest such chunk. Per-permutation traffic m(1+3/B)+n is strictly
    decreasing in B, so largest-feasible-B is the argmin."""
    m = condensed_size(n)
    for batch in _BATCH_CANDIDATES:
        for cand in _CHUNK_CANDIDATES:
            chunk, _ = snap_chunk(m, cand)
            cost = perm_batch_cost(n, batch, chunk, s)
            if (cost.resident_floats <= budget_floats
                    and chunk >= min(_MIN_CHUNK, m)):
                return batch, chunk
    # nothing fits at candidate granularity: close the form directly
    chunk, _ = snap_chunk(m, _MIN_CHUNK)
    return perm_batch_fit(n, chunk, budget_floats, s), chunk


def solve_tiles(n: int, d: Optional[int] = None, *,
                budget: Optional[BackendBudget] = None,
                profile: Optional[str] = None, s: int = 2) -> TunedTiles:
    """Solve every tile knob for a problem of ``n`` observations (and
    ``d`` features when feature-backed).

    ``s`` is the widest streamed-invariant stack the session may run
    (partial Mantel stacks 2 rows; sizing residency for the widest
    keeps one solve valid for the whole battery). K is deliberately not
    a parameter — see the module docstring. ``profile`` loads a
    ``save_profile`` JSON; explicit ``budget`` wins over it.
    """
    if n > MAX_TRIANGLE_N:
        raise ValueError(
            f"solve_tiles supports n <= {MAX_TRIANGLE_N} (int32 triangle "
            f"indexing would overflow in the permutation kernels); got "
            f"n={n}")
    if n < 1:
        raise ValueError(f"need n >= 1, got n={n}")
    if budget is None:
        budget = load_profile(profile) if profile else detect_budget()
    bf = budget.working_floats
    lane, floor = lane_geometry(None)

    # feature_block: start at the default (clamped to d) and SHRINK only
    # while even the smallest block cannot fit the production step
    fb = DEFAULT_FEATURE_BLOCK if d is None else max(
        min(DEFAULT_FEATURE_BLOCK, d), 1)
    if d:
        while (fb > _MIN_FEATURE_BLOCK
               and production_cost(n, d, pick_block(n, 8, lane, floor=floor),
                                   fb).resident_floats > bf):
            fb //= 2

    # block is shrink-only from the default (cap=DEFAULT_BLOCK): the
    # operator matvec re-associates row-panel partials, so a block the
    # default run never executed would move matvec-backed results off
    # bitwise. Modeled effective traffic loses nothing: an over-budget
    # default is priced at this same clamped geometry anyway.
    block = _fit_block(n, d, fb, lane, floor, bf, cap=DEFAULT_BLOCK)
    batch, chunk = _solve_batch_chunk(n, s, bf)
    batch = max(min(batch, BATCH_MAX), 1)

    def _modeled(blk, f_blk, bt, ck):
        # traffic is priced at the EFFECTIVE tiles under the budget —
        # a requested block/batch too big to stay resident realizes
        # only the reuse of the largest geometry that does fit, for the
        # solved and the hand-picked constants alike
        f_blk = max(min(f_blk, d), 1) if d else f_blk
        b_eff = _fit_block(n, d, f_blk, lane, floor, bf, cap=blk)
        out = {"perm_batch": perm_batch_cost(n, bt, ck, s,
                                             budget_floats=bf).to_dict(),
               "matvec": matvec_cost(n, 16, b_eff, lane=lane).to_dict()}
        if d:
            out["production"] = production_cost(n, d, b_eff,
                                                f_blk).to_dict()
        return out

    return TunedTiles(
        n=n, d=d, block=block, feature_block=fb, batch_size=batch,
        chunk=chunk, backend=budget.backend, budget=budget,
        modeled=_modeled(block, fb, batch, chunk),
        modeled_default=_modeled(DEFAULT_BLOCK, DEFAULT_FEATURE_BLOCK,
                                 DEFAULT_BATCH, DEFAULT_CHUNK))


def resolve_exec_config(config, n: int, d: Optional[int] = None):
    """Materialize an ``ExecConfig``'s auto knobs into concrete tiles.

    Returns ``(resolved_config, tuned)`` where ``resolved_config`` has
    every ``"auto"`` (or, under ``auto=True``, every left-at-default)
    knob replaced by the solved value — or ``(config, None)`` untouched
    when nothing asked for tuning. Explicitly-set concrete knobs are
    always honored, even under ``auto=True``.
    """
    import dataclasses as _dc

    auto_all = bool(getattr(config, "auto", False))

    def wants(name, default):
        v = getattr(config, name)
        return v == "auto" or (auto_all and v == default)

    want_block = wants("block", 256)
    want_fb = wants("feature_block", 128)
    want_batch = getattr(config, "batch_size") == "auto" or (
        auto_all and getattr(config, "batch_size") is None)
    want_chunk = getattr(config, "chunk") == "auto" or (
        auto_all and getattr(config, "chunk") is None)
    if not (want_block or want_fb or want_batch or want_chunk):
        return config, None

    tuned = solve_tiles(n, d, profile=getattr(config, "tune_profile", None))
    updates = {"auto": False}
    if want_block:
        updates["block"] = tuned.block
    if want_fb:
        updates["feature_block"] = tuned.feature_block
    if want_batch:
        updates["batch_size"] = tuned.batch_size
    if want_chunk:
        updates["chunk"] = tuned.chunk
    return _dc.replace(config, **updates), tuned
