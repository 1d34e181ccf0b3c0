"""Measured-vs-modeled reconciliation: the DriftSentinel.

``obs.probe`` reads what a compiled program actually moves;
``obs.ledger`` and ``tune.model`` say what the paper's streaming model
*prices*. This module closes the loop: every probe record is reconciled
against the analytic forms and judged against a per-backend tolerance
band, and the verdicts ride ``RunReport.drift`` — so "the model drifted
from the implementation" is a report field, not an archaeology project.

Two kinds of band, both calibrated against this container's XLA:CPU
(jax 0.4.37) and documented inline:

* **tight bands** where the closed form is exact — the scan-regime
  ``permute_reduce`` body (measured/modeled 0.93–0.98 across n=1024 and
  n=2048 and chunk sizes 8K–64K), the calibration stream pass (exactly
  1.0), and the peak-allocation models (argument + output + the known
  temp buffers);
* **envelope bands** where XLA's fusion policy picks the scale — below
  one chunk XLA may fuse an entire permute_reduce tile into one
  boundary-counted fusion (measured ≈ argument bytes, 0.14× the ledger
  floor at n=64) or materialize the gather stages (≈1.93× floor at
  n=128..256), and the production panel's metric intermediates either
  fuse (body ≈ n·d + 2·rb·n floats) or materialize at (rb, n, d)
  (body ≈ n·d + 4·rb·n·d). The verdict brackets measured between the
  cheapest and dearest known-good regime; anything outside — e.g. the
  square-gather permutation form at ~11× floor, or an accidental n×n
  materialization blowing the peak model — still fails loudly.

The ``ratio`` every verdict carries is measured / ledger-floor: the
implementation inflation factor over the paper's ideal streaming count.
On CPU the interesting ones are ~4.8 for the chunked permute_reduce
(HLO-level counting charges the permutation-index gathers and the
transposed gather output that the floor's per-element count does not)
and ceil(block/8)-flavored for the production panel (the XLA fallback
re-reads the full feature table once per 8-row sub-panel — exactly the
kind of fact a model-only report never surfaces).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

__all__ = ["DriftVerdict", "DriftSentinel", "reconcile"]

#: multiplicative slack applied to each envelope edge, per backend —
#: CPU edges were measured here; accelerator backends keep wider slack
#: until their fusion policies are calibrated the same way
_SLACK = {
    "cpu": (0.65, 1.35),
    "gpu": (0.5, 2.0),
    "tpu": (0.5, 2.0),
}
_DEFAULT_SLACK = (0.5, 2.0)

#: the dist XLA fallback's row sub-panel height (dist.driver._ROW_CHUNK)
_ROW_CHUNK_FALLBACK = 8


def _row_chunk() -> int:
    try:
        from repro.dist.driver import _ROW_CHUNK
        return int(_ROW_CHUNK)
    except Exception:
        return _ROW_CHUNK_FALLBACK


@dataclasses.dataclass(frozen=True)
class DriftVerdict:
    """One reconciled quantity for one probed entry point.

    ``floor`` is the analytic ideal (ledger traffic / modeled resident
    set); ``expected_lo``/``expected_hi`` the slack-adjusted envelope of
    known-good implementation regimes; ``ratio`` = measured / floor, the
    implementation inflation factor; ``within`` whether measured landed
    inside the envelope.
    """

    name: str
    quantity: str               # "bytes" | "peak"
    measured: float
    floor: float
    expected_lo: float
    expected_hi: float
    regime: str
    within: bool
    note: str = ""

    @property
    def ratio(self) -> float:
        return self.measured / self.floor if self.floor else float("inf")

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ratio"] = self.ratio
        return d


class DriftSentinel:
    """Reconciles ``obs.probe`` records against the analytic models.

    ``reconcile(records)`` takes the ``{name: ProbeRecord}`` mapping
    ``probe_session`` returns and emits the ``RunReport.drift`` section.
    Entry points without a closed-form counterpart (the engine's fused
    statistic programs, whose traffic depends on the statistic's own
    hoist structure) stay measured-only: present in ``measured``, no
    verdict here.
    """

    def __init__(self, backend: Optional[str] = None,
                 slack: Optional[tuple] = None):
        if backend is None:
            import jax
            backend = jax.default_backend()
        self.backend = backend
        self.slack = (tuple(slack) if slack is not None
                      else _SLACK.get(backend, _DEFAULT_SLACK))

    # -- helpers -----------------------------------------------------------
    def _verdict(self, name: str, quantity: str, measured: float,
                 floor: float, lo: float, hi: float, regime: str,
                 note: str = "") -> DriftVerdict:
        slo, shi = self.slack
        lo, hi = lo * slo, hi * shi
        return DriftVerdict(name=name, quantity=quantity,
                            measured=float(measured), floor=float(floor),
                            expected_lo=lo, expected_hi=hi, regime=regime,
                            within=bool(lo <= measured <= hi), note=note)

    # -- permute_reduce ----------------------------------------------------
    def check_permute_reduce(self, rec) -> List[DriftVerdict]:
        from repro.obs.ledger import perm_traffic_floats

        p = rec.params
        n, B = int(p["n"]), int(p["batch"])
        s, ch = int(p.get("s", 1)), int(p["chunk"])
        m = n * (n - 1) // 2
        scan = m > ch
        m_pad = -(-m // ch) * ch if scan else m
        args = 4.0 * (m * (3 + s) + B * n)          # xc+ii+jj, ys, orders
        out = 4.0 * B * s
        floor = 4.0 * B * s * perm_traffic_floats(n, B)["condensed_fused"]
        if scan:
            # per-chunk boundary floats: xc-gather out B·c, two
            # permutation-index gathers 2·B·c, transposed gather out
            # B·c, dot reads B·c + s·c, ii/jj/ys slices (2+s)·c —
            # (5B + 3s + 2)·c per iteration; entry pre-chunks ii/jj/ys
            # and reads xc: m·(6 + 2s). Measured/modeled 0.93–0.98.
            eff = 4.0 * (m_pad * (5 * B + 3 * s + 2) + m * (6 + 2 * s))
            bv = self._verdict("kernels.permute_reduce", "bytes",
                               rec.bytes_corrected, floor, eff, eff,
                               "scan",
                               "tight: scan-regime boundary form")
            temp = 4.0 * (3 * m_pad + B * ch)       # chunked ii/jj/ys + tile
        else:
            # envelope: whole-tile fusion (boundary = args+out) up to
            # materialized gather stages (~2x the ledger floor)
            bv = self._verdict("kernels.permute_reduce", "bytes",
                               rec.bytes_corrected, floor, args + out,
                               2.0 * floor, "single-chunk",
                               "envelope: fused .. materialized gathers")
            temp = 4.0 * B * m                      # one (B, m) gather
        pv = self._verdict("kernels.permute_reduce", "peak",
                           rec.peak_bytes, args + out + temp, args + out,
                           args + out + temp,
                           "scan" if scan else "single-chunk",
                           "args+out .. +known temp buffers")
        return [bv, pv]

    # -- distance production panel ----------------------------------------
    def check_panel(self, rec) -> List[DriftVerdict]:
        from repro.obs.ledger import production_floats

        p = rec.params
        n, d, b = int(p["n"]), int(p["d"]), int(p["block"])
        rb = _row_chunk()
        trips = -(-b // rb)
        args = 4.0 * (b * d + n * d)
        acc = 4.0 * b * n                           # (trips, rb, n) carry
        floor = 4.0 * production_floats(n, d, b) / max(-(-n // b), 1)
        fused = 4.0 * (n * d + 2 * rb * n + rb * d)
        mater = 4.0 * (n * d + 4 * rb * n * d + 2 * rb * n)
        bv = self._verdict(
            "dist.panel_stats", "bytes", rec.bytes_corrected, floor,
            trips * fused + args, trips * mater + args + floor, "lax.map",
            f"envelope: fused .. materialized metric body; x re-read "
            f"once per {rb}-row sub-panel")
        pv = self._verdict("dist.panel_stats", "peak", rec.peak_bytes,
                           args + acc, args + acc, args + 5 * acc,
                           "lax.map", "args + 1..5 accumulator buffers")
        return [bv, pv]

    # -- calibration stream pass -------------------------------------------
    def check_stream(self, rec) -> List[DriftVerdict]:
        nbytes = 8.0 * int(rec.params["n"])         # read + write fp32
        return [self._verdict("tune.stream_pass", "bytes",
                              rec.bytes_corrected, nbytes, nbytes, nbytes,
                              "stream", "tight: 2 passes exactly")]

    # -- front door --------------------------------------------------------
    _CHECKS = {
        "kernels.permute_reduce": "check_permute_reduce",
        "dist.panel_stats": "check_panel",
        "tune.stream_pass": "check_stream",
    }

    def reconcile(self, records: Dict[str, object]) -> dict:
        """``RunReport.drift`` section for a ``probe_session`` result."""
        verdicts: List[DriftVerdict] = []
        for name, rec in sorted(records.items()):
            method = self._CHECKS.get(name)
            if method is not None:
                verdicts.extend(getattr(self, method)(rec))
        return {
            "backend": self.backend,
            "slack": list(self.slack),
            "verdicts": [v.to_dict() for v in verdicts],
            "within_tolerance": all(v.within for v in verdicts),
        }


def reconcile(records: Dict[str, object],
              backend: Optional[str] = None) -> dict:
    """Module-level convenience: one-shot DriftSentinel reconcile."""
    return DriftSentinel(backend=backend).reconcile(records)
