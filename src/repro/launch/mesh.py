"""Production mesh construction (harness spec, MULTI-POD DRY-RUN §1).

``make_production_mesh`` is a FUNCTION — importing this module never
touches jax device state. Any caller that wants a simulated multi-pod
mesh must set ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before the first jax import; smoke tests and benchmarks see the real
single device. (The retired ``launch.dryrun`` was the last such caller;
nothing in-tree sets the override today.)

Hardware model: ``CHIP_PEAKS``, the published per-chip peaks keyed by
``jax.Device.device_kind``. ``chip_peaks`` refuses a kind that is not in
the table instead of guessing: a roofline priced against the wrong chip
is worse than none. ``tune.budget`` takes its TPU bandwidth from here.
"""

from __future__ import annotations

import jax

#: per-chip peaks by ``device_kind`` (Google Cloud documentation, "TPU
#: v5e"): bf16 FLOP/s, HBM bytes/s, HBM bytes, and interconnect bytes/s
#: per link (1,600 Gbit/s over four links)
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9, "ici_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """The ``CHIP_PEAKS`` row for ``device_kind``; unknown kinds raise."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}") from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over whatever devices exist (tests, examples)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_chips(mesh) -> int:
    return mesh.devices.size
