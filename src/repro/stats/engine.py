"""Shared permutation-test engine: paper §4.2's recipe, generalized.

The paper's Mantel speedup (Algorithm 3 → Algorithm 5) is really two
observations that apply to *every* distance-matrix permutation test:

1. **hoist** — most of each Monte-Carlo iteration is permutation-invariant
   (means, norms, ranks, the centered Gower matrix, group sizes). Compute
   those exactly once, outside the loop.
2. **fuse** — what remains per permutation should be a single pass over the
   matrix (one gather+multiply-reduce, or one small gather-matmul), not a
   chain of eager NumPy ops each costing a DRAM round-trip.

This module owns the loop so each statistic only declares the split:

* ``Statistic`` — the protocol: ``hoist() -> invariants`` runs once;
  ``per_perm(invariants, order) -> scalar`` runs K times inside a batched
  ``lax.map`` (and is auto-vmapped over each batch). Implementations are
  ``jax.tree_util.register_dataclass`` pytrees so the jitted engine caches
  its trace per statistic *class* (+ static metadata), not per call.
  The ``per_batch(invariants, orders) -> (B,)`` hook is the engine's
  PRIMARY execution path when a statistic defines it: the engine
  generates the (K, n) orders once, pads them up to full
  ``batch_size``-row tiles (wrapping real permutations, so ONE jit trace
  serves every K — no trailing-block recompile), and hands each tile to
  the statistic, which typically routes it through the batched
  ``repro.kernels.permute_reduce`` so the hoisted invariant streams once
  per tile instead of once per permutation. ``ExecConfig.batch_size`` is
  exactly the kernel's B grid dimension.
* ``permutation_test`` — permutation-order generation, batched execution,
  p-value finishing. Clients: ``core.mantel.mantel``, ``stats.permanova``,
  ``stats.anosim``, ``stats.partial_mantel``.
* ``permutation_test_distributed`` — the permutation axis through
  ``shard_map``, with a per-device ``fold_in`` exactly like
  ``core.mantel.mantel_distributed`` so the null distribution is
  mesh-shape-invariant (elastic-safe).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ExecConfig
from repro.obs.compile import note_trace
from repro.obs.trace import current_obs


# --------------------------------------------------------------------------
# RNG coercion — THE one documented key-handling rule for every entry point
# --------------------------------------------------------------------------
def as_key(key, default: int = 0) -> jax.Array:
    """Coerce ``key: jax.Array | int | None`` to a jax PRNG key.

    Every permutation-test and ordination entry point accepts any of:

    * ``None``            — the entry point's documented default seed
                            (``jax.random.PRNGKey(default)``);
    * a Python/NumPy int  — treated as a seed: ``PRNGKey(int(key))``;
    * a PRNG key array    — raw ``uint32[2]`` or new-style typed key,
                            passed through unchanged.

    This is the single home of the coercion rule; before it existed,
    ``seed`` ints and key arrays were accepted inconsistently across the
    API. Two calls with ``key=7`` and ``key=jax.random.PRNGKey(7)`` are
    guaranteed to draw identical permutations.
    """
    if key is None:
        return jax.random.PRNGKey(default)
    if isinstance(key, (int, np.integer)):
        return jax.random.PRNGKey(int(key))
    return jnp.asarray(key)


# --------------------------------------------------------------------------
# Protocol
# --------------------------------------------------------------------------
@runtime_checkable
class Statistic(Protocol):
    """A permutation-test statistic, split at the paper's hoisting boundary.

    ``n`` is the permutation domain size (number of samples). ``hoist``
    returns a pytree of permutation-invariant values, computed once per
    test; ``per_perm`` maps (invariants, order) to the scalar statistic and
    must be the *only* work that scales with K. The observed statistic is
    ``per_perm(invariants, identity)`` — one code path, no drift between
    observed and null evaluation.
    """

    n: int

    def hoist(self) -> Any: ...

    def per_perm(self, invariants: Any, order: jax.Array) -> jax.Array: ...


@dataclasses.dataclass(frozen=True)
class PermutationTestResult:
    """What every ``repro.stats`` test returns.

    ``method`` names the test ("permanova", "anosim", ...) and ``key``
    records the *resolved* RNG key (post ``as_key``) that drew the
    permutations — together with ``permutations`` they make the result
    self-describing and exactly replayable.
    """

    statistic: float
    p_value: float
    sample_size: int
    permutations: int
    method: str = ""
    key: Optional[jax.Array] = dataclasses.field(default=None, compare=False)


# --------------------------------------------------------------------------
# Pieces hoisted out of core/mantel.py (and generalized)
# --------------------------------------------------------------------------
def permutation_orders(key, permutations: int, n: int) -> jax.Array:
    """(K, n) int array of independent uniform permutations of range(n).

    One batched draw + one batched argsort (a random permutation is the
    argsort of iid random words) — ~2x faster than K vmapped
    ``random.permutation`` calls, which dispatch per-row threefry. A
    32-bit tie (probability ~n²/2³³ per row) resolves by stable sort
    order; at test resolution 1/(K+1) the bias is immaterial."""
    words = jax.random.bits(key, (permutations, n), dtype=jnp.uint32)
    return jnp.argsort(words, axis=-1)


def count_better(orig_stat: jax.Array, permuted_stats: jax.Array,
                 alternative: str) -> jax.Array:
    """How many null draws are at least as extreme as the observed value."""
    if alternative == "two-sided":
        return jnp.sum(jnp.abs(permuted_stats) >= jnp.abs(orig_stat))
    if alternative == "greater":
        return jnp.sum(permuted_stats >= orig_stat)
    if alternative == "less":
        return jnp.sum(permuted_stats <= orig_stat)
    raise ValueError(f"unknown alternative {alternative!r}")


def finish(orig_stat, permuted_stats, permutations: int, alternative: str,
           n: int, method: str = "",
           key: Optional[jax.Array] = None) -> PermutationTestResult:
    """Monte-Carlo p-value with the standard +1 correction. A NaN observed
    statistic propagates to a NaN p-value — NaN comparisons are all False,
    which would otherwise count zero exceedances and report the *most*
    significant p possible for a degenerate input."""
    c = int(count_better(orig_stat, permuted_stats, alternative))
    orig_stat = float(orig_stat)
    return PermutationTestResult(
        orig_stat,
        float("nan") if np.isnan(orig_stat) else p_value(c, permutations),
        n, permutations, method, key)


def p_value(count: int, permutations: int) -> float:
    """(count + 1) / (K + 1), divided on the host in fp32 — the one rule
    the engine and the serve scheduler share. A TPU divides through an
    approximate reciprocal: on the v5e it put 23/1000 one ulp off."""
    return float(np.float32(count + 1) / np.float32(permutations + 1))


# --------------------------------------------------------------------------
# The engine — plus the two tile-level entry points the serving front door
# (`repro.serve`) schedules through. `_null_distribution` remains the
# whole-test fast path; `hoist_and_observe` + `tile_statistics` expose the
# same split at tile granularity so a scheduler can interleave tiles from
# many concurrent requests while reusing the identical traces.
# --------------------------------------------------------------------------
@jax.jit
def hoist_and_observe(stat):
    """``(invariants, observed)`` for ``stat``, one jit region.

    The hoist and the identity-order observed evaluation fuse together
    (the identity gathers fold away instead of materializing full n×n
    copies eagerly). Shared by the distributed engine and by
    ``repro.serve`` admission, which hoists once per pooled session and
    then streams tiles through ``tile_statistics``.
    """
    note_trace("stats.engine.hoist_and_observe",
               (type(stat).__name__, stat.n), hoist_and_observe, (stat,))
    with jax.named_scope("perm.hoist"):
        inv = stat.hoist()
        return inv, stat.per_perm(inv, jnp.arange(stat.n))


@jax.jit
def tile_statistics(stat, invariants, orders):
    """(B,) null statistics for one padded tile of permutation orders.

    The serve scheduler's execution primitive: every tile it assembles —
    regardless of which requests' permutations fill the rows — runs
    through this one trace per (statistic class, n, B) signature, so the
    one-program-per-K sentinel invariant extends across requests. Rows
    are independent (``per_batch`` reduces each order against the same
    hoisted invariants), which is what makes coalescing bitwise-neutral:
    a request's draws do not depend on its tile-mates.
    """
    note_trace("stats.engine.tile",
               (type(stat).__name__, stat.n, orders.shape[0]),
               tile_statistics, (stat, invariants, orders))
    per_batch = getattr(stat, "per_batch", None)
    with jax.named_scope("perm.draws"):
        if per_batch is not None:
            return per_batch(invariants, orders)
        return jax.vmap(lambda o: stat.per_perm(invariants, o))(orders)


@partial(jax.jit, static_argnames=("permutations", "batch_size"))
def _null_distribution(stat, key, permutations: int, batch_size: int):
    """observed statistic + (K,) null draws, one jit region.

    ``stat`` is a pytree: its arrays are traced, its static metadata (n,
    group count, …) keys the jit cache, so repeated tests of the same
    shape reuse the compiled executable.

    Three ``jax.named_scope``s split the program for the profiler, named
    after what the test defines rather than how it is computed:
    ``perm.orders`` (drawing the orders and padding them to whole
    tiles), ``perm.hoist`` (the invariants and the observed statistic)
    and ``perm.draws`` (the K null draws).
    """
    # trace-time only (a jitted body runs once per distinct signature):
    # the sentinel's count of engine programs, free at execution time
    note_trace("stats.engine.null_distribution",
               (type(stat).__name__, stat.n, permutations, batch_size),
               _null_distribution, (stat, key),
               {"permutations": permutations, "batch_size": batch_size})
    with jax.named_scope("perm.hoist"):
        invariants = stat.hoist()                  # runs exactly once
        observed = stat.per_perm(invariants, jnp.arange(stat.n))

    with jax.named_scope("perm.orders"):
        orders = permutation_orders(key, permutations, stat.n)
    per_batch = getattr(stat, "per_batch", None)
    if per_batch is not None and permutations:
        # ONE trace serves every K: orders are padded up to full
        # batch_size tiles by wrapping real permutations (each row must
        # stay a valid order for the statistic's gathers), every tile
        # goes through the same per_batch trace, and the padded tail is
        # masked off before finishing. The pre-PR-5 trailing-block
        # special case traced a SECOND jit program whenever batch_size
        # didn't divide K (the canonical 999 vs batch 32) — same math,
        # double the compile time and cache footprint.
        # K is deliberately NOT in this signature: the padded path's
        # contract is that programs stays 1 across every K at fixed
        # (statistic, n, B) — the sentinel makes that assertable
        note_trace("stats.engine.per_batch",
                   (type(stat).__name__, stat.n, batch_size))
        num_tiles = -(-permutations // batch_size)
        total = num_tiles * batch_size
        with jax.named_scope("perm.orders"):
            if total != permutations:
                orders = orders[jnp.arange(total) % permutations]
            tiles = orders.reshape(num_tiles, batch_size, stat.n)
        with jax.named_scope("perm.draws"):
            permuted = jax.lax.map(lambda o: per_batch(invariants, o),
                                   tiles).reshape(total)[:permutations]
    else:
        # lax.map auto-vmaps per_perm over each batch: the batched gathers
        # + one fused reduce, with peak memory of one batch of matrices.
        with jax.named_scope("perm.draws"):
            permuted = jax.lax.map(lambda o: stat.per_perm(invariants, o),
                                   orders, batch_size=batch_size)
    return observed, permuted


def permutation_test(stat: Statistic, permutations: int = 999,
                     key=None, alternative: str = "two-sided",
                     batch_size: Optional[int] = None,
                     config: Optional[ExecConfig] = None,
                     method: str = "") -> PermutationTestResult:
    """Run a hoisted+fused Monte-Carlo permutation test for ``stat``.

    ``key`` follows the unified coercion rule (``as_key``: key array, int
    seed, or None -> PRNGKey(0)). ``batch_size`` resolves as explicit arg >
    ``config.batch_size`` > 8; a still-unresolved ``"auto"`` (a config
    that never went through ``ExecConfig.resolve``/Workspace admission)
    is solved here against the statistic's n — from (n, budget) only,
    never K, so the one padded per-batch program keeps serving every K.
    ``method`` is recorded on the result.
    """
    if alternative not in ("two-sided", "greater", "less"):
        raise ValueError(f"unknown alternative {alternative!r}")
    key = as_key(key)
    bs = (config or ExecConfig()).resolve_batch_size(batch_size, 8)
    if bs == "auto":
        from repro.tune.solve import solve_tiles
        bs = solve_tiles(stat.n).batch_size
    obs = current_obs()          # the ambient session (NULL_OBS when none)
    batched = getattr(stat, "per_batch", None) is not None
    tiles = -(-permutations // bs) if permutations else 0
    with obs.span(f"engine.{method or type(stat).__name__}",
                  phase="per_perm", n=stat.n, permutations=permutations,
                  batch_size=bs, tiles=tiles, batched=batched,
                  layout=getattr(stat, "layout", None)):
        observed, permuted = _null_distribution(stat, key, permutations, bs)
    if batched and permutations:
        # the batched loop IS the condensed_fused traffic model — the
        # padded tail rows are real gathers, so they are charged too
        obs.charge_perm_batch(method or type(stat).__name__, stat.n,
                              tiles * bs, bs)
    with obs.span("engine.finish", n=stat.n, permutations=permutations):
        # the p-value's count waits for the draws: the blocking fetch
        return finish(observed, permuted, permutations, alternative,
                      stat.n, method=method, key=key)


# --------------------------------------------------------------------------
# Distributed engine — permutation axis through shard_map
# --------------------------------------------------------------------------
def null_distribution_distributed(stat: Statistic, mesh,
                                  permutations: int = 1024, key=None,
                                  perm_axes=("data",),
                                  batch_size: Optional[int] = None,
                                  config: Optional[ExecConfig] = None):
    """``(observed, null)`` with K/|devices| permutations per device.

    The invariants are hoisted once and replicated; each device draws its
    own permutations via ``fold_in(key, device_index)`` — the same
    elastic-safe construction as ``mantel_distributed``, so the global
    null distribution does not depend on the mesh shape. ``null`` is the
    (K,) array sharded over ``perm_axes``: device ``d`` holds the draws
    of its ``fold_in(key, d)`` orders, in row-major device order.
    """
    from jax.sharding import PartitionSpec as P

    key = as_key(key)
    batch_size = (config or ExecConfig()).resolve_batch_size(batch_size, 8)

    n_perm_devices = int(np.prod([mesh.shape[a] for a in perm_axes]))
    if permutations % n_perm_devices:
        raise ValueError(f"permutations ({permutations}) must divide over "
                         f"{n_perm_devices} devices")
    per_dev = permutations // n_perm_devices

    invariants, observed = hoist_and_observe(stat)

    def _local(st, inv):
        dev = 0                     # row-major rank over ALL perm axes, so
        for a in perm_axes:         # no two devices fold_in the same index
            dev = dev * mesh.shape[a] + jax.lax.axis_index(a)
        k = jax.random.fold_in(key, dev)
        orders = permutation_orders(k, per_dev, st.n)
        return jax.lax.map(lambda o: st.per_perm(inv, o), orders,
                           batch_size=min(batch_size, per_dev))

    # the statistic's arrays enter as (replicated) arguments: arrays closed
    # over by a shard_map body would stay committed to one device
    f = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P()),                 # statistic + invariants replicated
        out_specs=P(perm_axes[0] if len(perm_axes) == 1 else perm_axes),
    )
    return observed, f(stat, invariants)


def permutation_test_distributed(stat: Statistic, mesh,
                                 permutations: int = 1024,
                                 key=None,
                                 alternative: str = "two-sided",
                                 perm_axes=("data",),
                                 batch_size: Optional[int] = None,
                                 config: Optional[ExecConfig] = None,
                                 method: str = "") -> PermutationTestResult:
    """Permutation-parallel engine: K/|devices| permutations per device
    (``null_distribution_distributed``), finished like the host engine."""
    if alternative not in ("two-sided", "greater", "less"):
        raise ValueError(f"unknown alternative {alternative!r}")
    key = as_key(key)
    observed, permuted = null_distribution_distributed(
        stat, mesh, permutations, key, perm_axes=perm_axes,
        batch_size=batch_size, config=config)
    return finish(observed, permuted, permutations, alternative, stat.n,
                  method=method, key=key)


# --------------------------------------------------------------------------
# Shared helpers for grouping-based statistics (PERMANOVA, ANOSIM)
# --------------------------------------------------------------------------
def encode_grouping(grouping) -> tuple[np.ndarray, int]:
    """Map arbitrary hashable labels to int codes in [0, num_groups)."""
    codes = np.unique(np.asarray(grouping), return_inverse=True)[1]
    num_groups = int(codes.max()) + 1
    if num_groups < 2:
        raise ValueError("grouping must contain at least two groups")
    if num_groups == codes.size:
        raise ValueError("grouping must have at least one group of size > 1")
    return codes.astype(np.int32), num_groups
