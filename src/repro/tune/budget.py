"""Backend byte budgets: what "fits in fast memory" means, per backend.

``detect_budget()`` answers the question every hand-picked tile constant
in this repo used to answer implicitly: how many bytes may a kernel's
working set occupy and still stream at full bandwidth? On TPU that is
the VMEM budget the Pallas kernels double-buffer inside (16 MiB/core on
v5e — the same figure ``kernels.center_ops`` documents for its 512 tile
default); on CPU it is an L2-class working budget (the interpreter and
the XLA scan paths live or die by L2 residency of the per-step tile);
on GPU an L2-class slice.

``calibrate()`` upgrades the static bandwidth/latency defaults to
measured ones with a two-point timed probe (one small buffer dominated
by dispatch latency, one large buffer dominated by stream bandwidth —
a two-unknown linear fit, exactly the roofline decomposition
``launch.mesh`` models statically). Profiles round-trip through JSON so
CI can persist a container's calibration as an artifact and later runs
can ``load_profile()`` instead of re-probing.

The solver consumes budgets in fp32 floats: ``budget.working_floats``.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.launch.mesh import chip_peaks

__all__ = ["BackendBudget", "detect_budget", "calibrate",
           "save_profile", "load_profile"]

#: bytes per fp32 element — every budget below is quoted in bytes and
#: converted via this
_FP32 = 4


@dataclasses.dataclass(frozen=True)
class BackendBudget:
    """One backend's memory-system description, as the solver sees it.

    * ``working_bytes`` — the budget a single kernel step's tunable
      resident set must fit (VMEM on TPU, an L2-class slice on CPU/GPU);
    * ``capacity_bytes`` — the larger next-level pool (HBM/L3): only
      used for sanity bounds, never for tile fitting;
    * ``bandwidth`` / ``latency`` — stream bandwidth (bytes/s) and
      per-dispatch latency (s), static defaults unless calibrated;
    * ``source`` — ``"default"``, ``"calibrated"`` or ``"profile"``.
    """

    backend: str
    working_bytes: int
    capacity_bytes: int
    bandwidth: float
    latency: float
    source: str = "default"

    @property
    def working_floats(self) -> float:
        return self.working_bytes / _FP32

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "BackendBudget":
        return BackendBudget(**d)


#: static per-backend defaults. TPU rows are keyed by ``device_kind``:
#: the v5e's default scoped VMEM (16 MiB) over its 16 GiB HBM, streamed
#: at the published HBM bandwidth (``launch.mesh.CHIP_PEAKS``). CPU: a
#: conservative 1 MiB L2 working slice (per-core L2 is 0.5–2 MiB across
#: the x86 fleet; the solver prefers tiles that fit the SMALL end so they
#: fit everywhere) over a 32 MiB L3. GPU: an 8 MiB L2 slice over HBM.
_TPU_DEFAULTS = {
    "TPU v5 lite": dict(working_bytes=16 * 2**20, capacity_bytes=16 * 2**30,
                        bandwidth=chip_peaks("TPU v5 lite")["hbm_bw"],
                        latency=3e-6),
}
_DEFAULTS = {
    "cpu": dict(working_bytes=1 * 2**20, capacity_bytes=32 * 2**20,
                bandwidth=3e10, latency=30e-6),
    "gpu": dict(working_bytes=8 * 2**20, capacity_bytes=2 * 2**30,
                bandwidth=9e11, latency=5e-6),
}


def detect_budget(backend: Optional[str] = None,
                  device_kind: Optional[str] = None) -> BackendBudget:
    """The static budget for ``backend`` (default: the live
    ``jax.default_backend()``). A TPU is looked up by ``device_kind``
    (default: the first live device's). An unknown backend or TPU kind
    raises: tiles sized for another chip's memory would be silently
    wrong."""
    be = backend or jax.default_backend()
    if be == "tpu":
        kind = device_kind or jax.devices()[0].device_kind
        row = _TPU_DEFAULTS.get(kind)
        if row is None:
            raise ValueError(f"no budget for TPU device kind {kind!r}; "
                             f"known: {sorted(_TPU_DEFAULTS)}")
    else:
        row = _DEFAULTS.get(be)
        if row is None:
            raise ValueError(f"no budget for backend {be!r}; known: "
                             f"{sorted(_DEFAULTS) + ['tpu']}")
    return BackendBudget(backend=be, source="default", **row)


def _time_pass(x: jax.Array, reps: int = 5) -> float:
    """Median seconds for one jitted elementwise pass over ``x``."""
    f = jax.jit(lambda a: a * 2.0 + 1.0)
    f(x).block_until_ready()                         # compile outside timing
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def calibrate(base: Optional[BackendBudget] = None, *,
              small: int = 1 << 12, large: int = 1 << 22,
              reps: int = 5, mode: str = "wall") -> BackendBudget:
    """Fit the budget's rate constants from streaming probes.

    ``mode="wall"`` (the historical path) times two jitted passes — one
    small (latency-dominated), one large (bandwidth-dominated) — and
    solves the two-point linear fit. A pass over N floats costs
    ``latency + bytes/bandwidth``. Wall timings inherit this container's
    ±40% noise, which is why nothing downstream gates on them.

    ``mode="probe"`` is deterministic: it compiles the SAME pass
    ahead-of-time (``obs.probe.probe_stream_pass``) and reads the
    compiled program's scan-corrected byte count instead of the clock.
    The effective bandwidth is the backend default scaled by
    naive/measured bytes — if XLA's compiled pass moves more bytes than
    the 2-per-element model assumes (extra copies, padding), the solver
    should price streams proportionally slower. Latency keeps the
    backend default (dispatch latency has no compile-time observable).
    Same answer on every run of a container image, immune to noisy
    neighbors; ``source="probed"``.

    Either mode returns a new budget with measured constants; capacities
    stay the static per-backend values (probing cache SIZES from
    wall-clock is exactly the noise this repo's analytic-gate policy
    avoids — so only rate constants are ever measured).
    """
    b = base or detect_budget()
    if mode == "probe":
        from repro.obs.probe import probe_stream_pass
        rec = probe_stream_pass(large)
        naive = 2.0 * _FP32 * large
        factor = max(rec.bytes_corrected / naive, 1e-6)
        return dataclasses.replace(b, bandwidth=b.bandwidth / factor,
                                   source="probed")
    if mode != "wall":
        raise ValueError(f"calibrate mode must be 'wall' or 'probe', "
                         f"got {mode!r}")
    t_small = _time_pass(jnp.ones((small,), jnp.float32), reps)
    t_large = _time_pass(jnp.ones((large,), jnp.float32), reps)
    # each element moves ~2 fp32 (read + write) per pass
    bytes_small, bytes_large = 2 * _FP32 * small, 2 * _FP32 * large
    dt = max(t_large - t_small, 1e-12)
    bandwidth = (bytes_large - bytes_small) / dt
    latency = max(t_small - bytes_small / bandwidth, 0.0)
    return dataclasses.replace(b, bandwidth=bandwidth, latency=latency,
                               source="calibrated")


def save_profile(budget: BackendBudget, path: str) -> None:
    """Persist a budget (typically a calibrated one) as JSON."""
    with open(path, "w") as f:
        json.dump(budget.to_dict(), f, indent=2)


def load_profile(path: str) -> BackendBudget:
    """Reload a ``save_profile`` JSON; source becomes ``"profile"``."""
    with open(path) as f:
        d = json.load(f)
    d["source"] = "profile"
    return BackendBudget.from_dict(d)
