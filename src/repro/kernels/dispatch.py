"""Shared backend-dispatch and tile-snapping policy for the kernels.

* **interpret resolution** and **lane geometry** — ``None`` means
  "TPU-native on a TPU backend, the Pallas interpreter everywhere else";
  TPU-native tiles need lane-aligned (multiple of 128) trailing dims
  while the interpreter takes the fp32 sublane multiple of 8. Only the
  Pallas kernels no analysis path calls (``center``, ``symhollow``,
  ``mantel_corr``, ``rmsnorm``) dispatch on them;
* **tile snapping** — ``pick_block``: the largest multiple-of-lane block
  ``<=`` requested, clamped to the problem size, with a floor for tiny
  inputs; ``clamp_block`` is the un-laned clamp the XLA row panels use;
* **chunk geometry** — ``snap_chunk`` for the condensed streams of
  ``permute_reduce``.

``repro.tune`` and ``Workspace.resolved_tiles`` snap with
``lane_geometry(None)`` as well: the lane of those Pallas kernels, which
the XLA bodies of the analysis path (``clamp_block``) do not use.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

#: the precision of every fp32 matmul and dot on the analysis path. A
#: TPU's default runs fp32 operands as one bf16 pass, which moved the
#: v5e's observed Mantel r by 2e-4 and PERMDISP's F by 1e-4 of their
#: float64 values; XLA:CPU computes fp32 either way.
HIGHEST = jax.lax.Precision.HIGHEST

#: fp32 sublane multiple — the snap unit in interpreter mode (and for
#: row-ish dims everywhere).
SUBLANE = 8
#: TPU-native Mosaic lane width — trailing tile dims must be multiples
#: of this when ``interpret=False`` resolves on a TPU backend.
TPU_LANE = 128


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = auto: native on TPU, interpreter everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def lane_geometry(interpret: Optional[bool]) -> Tuple[int, int]:
    """(lane, floor) for trailing tile dims under the resolved dispatch
    mode: interpreter tiles snap to the fp32 sublane (8) and may shrink
    to 1 for tiny inputs; TPU-native tiles must stay lane-legal (128
    both as snap unit and floor)."""
    if resolve_interpret(interpret):
        return SUBLANE, 1
    return TPU_LANE, TPU_LANE


def pick_block(n: int, requested: int, lane: int = SUBLANE,
               floor: int = 1) -> int:
    """Largest multiple-of-``lane`` block <= requested (tiny n falls back
    to ``floor``; native TPU callers pass floor=lane to keep tiles
    lane-legal). THE single home of the lane-snapping rule — every
    kernel dispatcher and the ``repro.tune`` solver route through it, so
    modeled tiles and executed tiles are the same numbers."""
    b = min(requested, n)
    if b >= lane:
        b -= b % lane
    return max(b, floor)


def clamp_block(n: int, requested: int) -> int:
    """The un-laned clamp used by the pure-XLA row-panel paths
    (``dist.driver``, the operator row blocks): any block in [1, n] is
    legal there, so the policy is just ``max(min(requested, n), 1)``."""
    return max(min(requested, n), 1)


def snap_chunk(m: int, chunk: int) -> Tuple[int, int]:
    """(chunk, m_pad) for a 1-D condensed stream of length ``m``: snap
    the chunk to the 8-aligned condensed length so tiny problems don't
    pad 630 entries up to 65536, then pad ``m`` up to a chunk multiple.
    Shared by ``permute_reduce_ops`` and the tuner's chunk model."""
    m8 = -(-max(m, 1) // SUBLANE) * SUBLANE
    chunk = max(min(chunk, m8), 1)
    return chunk, -(-m // chunk) * chunk
