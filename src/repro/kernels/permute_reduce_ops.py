"""jit'd public wrapper for the batched permuted-gather-reduce.

One entry point, two implementations with identical semantics and the
same analytic traffic profile (the tests pin them against each other and
against ``permute_reduce_ref``):

* ``impl="pallas"`` — the explicit-VMEM kernel in ``permute_reduce.py``
  (TPU-native when ``jax.default_backend() == "tpu"``, the interpreter
  elsewhere, like every kernel in this package);
* ``impl="xla"``   — a ``lax.scan`` over the same condensed chunks: the
  streamed invariants enter one (S, chunk) tile at a time, the permuted
  gather is a single vectorized (B, chunk) take, and the multiply-reduce
  is one small matmul. Peak extra memory is one (B, chunk) gather tile —
  never (B, m), and never any n² buffer. This is the production CPU
  path (XLA:CPU vectorizes the gather; the Pallas interpreter does not).

The wrapper owns the hoistable geometry: the triangle coordinate map
(ii, jj) via ``triangle_coords`` — callers may pass a precomputed pair to
keep it inside their own hoist — plus chunk padding (padded positions
carry zero ``ys``, so they contribute exactly 0) and the int32 bound
(``n <= MAX_TRIANGLE_N``; beyond it the closed-form index would wrap and
CLAMP into silently wrong gathers, so we refuse loudly like
``CondensedCenteredGramOperator``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import HIGHEST, snap_chunk
from repro.kernels.permute_reduce import permute_reduce_kernel
from repro.obs.compile import note_trace

# condensed chunk streamed per grid step. 64k floats = 256 KiB per ys row:
# big enough that the (B, chunk) gather tile amortizes loop overhead,
# small enough to stay cache/VMEM-resident alongside the xc block.
# ``repro.tune`` solves this knob from the measured budget instead when
# ``ExecConfig(auto=True)``; callers pass chunk=None to keep the default.
DEFAULT_CHUNK = 65536
_DEFAULT_CHUNK = DEFAULT_CHUNK            # backward-compat alias

# the chunk/padding geometry is the shared ``kernels.dispatch.snap_chunk``
# policy (also consumed by the tuner's resident-set model)
_chunk_geometry = snap_chunk


def _reduce_xla(xc, ys, ii, jj, orders, n: int, chunk: int) -> jax.Array:
    """The lax.scan twin: same chunking, same math, pure XLA."""
    s, m_pad = ys.shape
    num_chunks = m_pad // chunk
    ii_c = ii.reshape(num_chunks, chunk)
    jj_c = jj.reshape(num_chunks, chunk)
    ys_c = jnp.moveaxis(ys.reshape(s, num_chunks, chunk), 1, 0)

    def body(acc, operands):
        ic, jc, yc = operands                      # (chunk,), (S, chunk)
        oi = jnp.take(orders, ic, axis=1)          # (B, chunk) order gather
        oj = jnp.take(orders, jc, axis=1)
        lo = jnp.minimum(oi, oj)
        hi = jnp.maximum(oi, oj)
        k = lo * (2 * n - lo - 1) // 2 + (hi - lo - 1)
        xg = jnp.take(xc, k)                       # (B, chunk) xc gather
        return acc + jnp.matmul(yc, xg.T, precision=HIGHEST), None

    acc0 = jnp.zeros((s, orders.shape[0]), dtype=xc.dtype)
    out, _ = jax.lax.scan(body, acc0, (ii_c, jj_c, ys_c))
    return out


@partial(jax.jit, static_argnames=("impl", "chunk", "interpret"))
def _permute_reduce_jit(xc: jax.Array, ys: jax.Array, orders: jax.Array,
                        ii: Optional[jax.Array], jj: Optional[jax.Array], *,
                        impl: str, chunk: int,
                        interpret: Optional[bool]) -> jax.Array:
    """All B permuted condensed multiply-reduces of one invariant stack.

    out[s, b] = sum_k ys[s, k] * xc[tri(orders[b, i_k], orders[b, j_k])]
              = <condensed(X[orders[b]][:, orders[b]]), ys[s]>

    xc: (m,) condensed source, m = n(n-1)/2. ys: (S, m) permutation-
    invariant streams (S reductions share ONE gather). orders: (B, n)
    int permutation tile. ii/jj: optional precomputed ``triangle_coords``
    (hoist them once per test; recomputed here when omitted).
    Returns (S, B) in xc's dtype.

    This is the jitted body — call through ``permute_reduce``, which owns
    the chunk-default normalization (so ``chunk=None`` and an explicit
    ``chunk=DEFAULT_CHUNK`` share ONE jit cache entry and one sentinel
    program).
    """
    # deferred: importing repro.core at module scope would cycle through
    # the package inits (core → mantel → stats → kernels)
    from repro.core.distance_matrix import MAX_TRIANGLE_N, triangle_coords

    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown permute_reduce impl {impl!r}")
    b_perms, n = orders.shape
    if n > MAX_TRIANGLE_N:
        raise ValueError(
            f"permute_reduce supports n <= {MAX_TRIANGLE_N} (int32 "
            f"triangle indexing would overflow and silently corrupt the "
            f"gather); got n={n}")
    m = n * (n - 1) // 2
    if xc.shape != (m,):
        raise ValueError(f"xc must be condensed length m={m} for n={n}, "
                         f"got {xc.shape}")
    if ys.ndim != 2 or ys.shape[1] != m:
        raise ValueError(f"ys must be (S, {m}), got {ys.shape}")
    # trace-time only: THE padded per_batch kernel entry — one program
    # per (n, B, S, impl, chunk) whatever K the engine runs (nested-jit
    # bodies trace once per distinct avals even across outer retraces)
    note_trace("kernels.permute_reduce",
               (n, b_perms, ys.shape[0], impl, chunk, interpret))
    if m == 0:                                     # n < 2: empty triangle
        return jnp.zeros((ys.shape[0], b_perms), dtype=xc.dtype)

    if ii is None or jj is None:
        ii, jj = triangle_coords(n)
    orders = orders.astype(jnp.int32)
    ii = ii.astype(jnp.int32)
    jj = jj.astype(jnp.int32)

    chunk, m_pad = _chunk_geometry(m, chunk)
    pad = m_pad - m
    if pad:
        # padded ys is zero ⇒ padded positions contribute exactly 0; the
        # padded coords are the valid pair (0, 1) so the dead gather stays
        # in range instead of wrapping
        ys = jnp.pad(ys, ((0, 0), (0, pad)))
        ii = jnp.pad(ii, (0, pad))
        jj = jnp.pad(jj, (0, pad), constant_values=1)

    if impl == "pallas":
        return permute_reduce_kernel(xc, ys, ii, jj, orders, chunk=chunk,
                                     interpret=interpret)
    return _reduce_xla(xc, ys, ii, jj, orders, n, chunk)


def permute_reduce(xc: jax.Array, ys: jax.Array, orders: jax.Array,
                   ii: Optional[jax.Array] = None,
                   jj: Optional[jax.Array] = None, *, impl: str = "xla",
                   chunk: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """All B permuted condensed multiply-reduces of one invariant stack
    (see ``_permute_reduce_jit`` for the exact semantics and shapes).

    ``chunk=None`` keeps ``DEFAULT_CHUNK``; the ``repro.tune`` solver
    passes a budget-solved value instead. Normalizing here — outside the
    jit boundary — keeps None and the explicit default on one cache
    entry and one sentinel program.
    """
    return _permute_reduce_jit(
        xc, ys, orders, ii, jj, impl=impl,
        chunk=DEFAULT_CHUNK if chunk is None else int(chunk),
        interpret=interpret)
