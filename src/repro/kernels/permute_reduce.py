"""Pallas kernel: batched permuted-gather-reduce over CONDENSED storage.

The last square-matrix dependence of the Mantel-family hot loop (paper
§4.2, Algorithm 5). The engine's PR-4 loop materialized the permuted
square ``X[o][:, o]`` and streamed a square invariant per permutation —
~6·n² floats of traffic each. But the statistic only needs the condensed
inner product, and the condensed form of the permuted matrix is a pure
index transform of the condensed original:

    condensed(X_p)[k] = xc[ tri(order[i_k], order[j_k]) ]
    tri(i, j)         = lo*(2n - lo - 1)/2 + (hi - lo - 1)

so the whole per-permutation pass is one closed-form gather + one fused
multiply-reduce over m = n(n-1)/2 entries. This kernel batches it:

* grid = (condensed chunks, B permutations), **b innermost** — the chunk
  of the streamed invariants (``ys``, plus the hoisted triangle map
  ``ii``/``jj``) is fetched into VMEM once per chunk and reused across
  all B permutations (Pallas elides the re-fetch while the BlockSpec
  index is unchanged between consecutive steps), exactly the Ŷ-tile
  trick of ``mantel_corr`` — minus the n² gather buffer;
* the condensed source ``xc`` is a single VMEM-resident block (it is the
  *only* large buffer left: half a square's bytes);
* per (chunk, b) step: gather ``order`` at the chunk's triangle coords,
  fold through ``tri``, gather ``xc`` in-register, multiply-reduce
  against every ``ys`` row, accumulate into the (S, 1) output block.

Analytic traffic per permutation: m (its own xc gather) + 3m/B (its
share of the ys/ii/jj streams) + n (its order row) — vs the square
loop's ~6n² ≈ 12m. S invariant rows share one gather, so the partial
Mantel test's two reductions cost one pass.

``interpret=None`` dispatches by backend like every kernel here: the
Pallas interpreter off the TPU (the production path everywhere is the
pure-XLA twin in ``permute_reduce_ops``). There is no TPU lowering yet:
native mode raises ``NotImplementedError`` instead of falling back.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret


def _permute_reduce_kernel(n, ii_ref, jj_ref, ys_ref, orders_ref, xc_ref,
                           out_ref):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():                 # first visit of this permutation's block
        out_ref[...] = jnp.zeros_like(out_ref)

    order = orders_ref[0]        # (n,)   — this permutation's order row
    i_idx = ii_ref[...]          # (bk,)  — chunk triangle rows, streamed
    j_idx = jj_ref[...]          # (bk,)  — chunk triangle cols, streamed
    # closed-form triangle index, inlined (importing core.distance_matrix
    # at kernel module scope would cycle core → mantel → stats → kernels);
    # int32-exact for n <= MAX_TRIANGLE_N, enforced by the ops wrapper
    oi, oj = order[i_idx], order[j_idx]
    lo = jnp.minimum(oi, oj)
    hi = jnp.maximum(oi, oj)
    k = lo * (2 * n - lo - 1) // 2 + (hi - lo - 1)
    xg = xc_ref[...][k]          # in-register permuted-condensed gather
    ys = ys_ref[...]             # (S, bk) — shared, VMEM-resident across b
    out_ref[...] += jnp.sum(ys * xg[None, :], axis=1)[:, None]


def permute_reduce_kernel(xc: jax.Array, ys: jax.Array, ii: jax.Array,
                          jj: jax.Array, orders: jax.Array, *, chunk: int,
                          interpret: Optional[bool] = None) -> jax.Array:
    """out[s, b] = sum_k ys[s, k] * xc[tri(orders[b, ii[k]], orders[b, jj[k]])].

    xc: (m,) condensed source (gathered; the only unchunked operand).
    ys/ii/jj: (S, m_pad)/(m_pad,)/(m_pad,) streamed invariants, m_pad a
    multiple of ``chunk`` with zero-``ys`` padding (the ops wrapper owns
    the padding so padded positions contribute exactly 0). orders: (B, n)
    int32. Returns (S, B) in xc's dtype.
    """
    interpret = resolve_interpret(interpret)
    if not interpret:
        raise NotImplementedError(
            "the Pallas permute_reduce kernel does not compile for TPU: "
            "its 1-D chunk blocks and (1, n) order block break Mosaic's "
            "(8, 128) tiling, it gathers in-kernel from a whole-(m,) VMEM "
            "block of the condensed source, and that block outgrows VMEM "
            "at real n. Use ExecConfig(kernel='xla'), the default.")
    s, m_pad = ys.shape
    b_perms, n = orders.shape
    m = xc.shape[0]
    grid = (m_pad // chunk, b_perms)     # b innermost → chunk-stream reuse
    return pl.pallas_call(
        partial(_permute_reduce_kernel, n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk,), lambda c, b: (c,)),
            pl.BlockSpec((chunk,), lambda c, b: (c,)),
            pl.BlockSpec((s, chunk), lambda c, b: (0, c)),
            pl.BlockSpec((1, n), lambda c, b: (b, 0)),
            pl.BlockSpec((m,), lambda c, b: (0,)),
        ],
        out_specs=pl.BlockSpec((s, 1), lambda c, b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((s, b_perms), xc.dtype),
        interpret=interpret,
    )(ii, jj, ys, orders, xc)
