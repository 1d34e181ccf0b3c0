#!/usr/bin/env python3
"""The readings that the output check's limits are set from.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,...,12 --seconds 20 [--control bfloat16 \
        --control-seeds 4]

For each seed, in one process (the programs compile once): the cell's
set-up, a window of ``--seconds`` at the cell's own load, and then the
comparison of what the window produced with the float64 reference — the
program's reading. With ``--control``, the same answers are also
computed by the reference in that precision and compared in the
program's place — the control's reading, which has to fail. One JSON
line per seed and side; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also run the control")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from benchmarks.chip import harness
    cell = harness.Cell(args.workload)
    harness.devices_for(cell, require_tpu=True)
    harness.enable_compile_cache()
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.time()
        drv = harness.driver_module(cell).Driver(cell, seed, args.seconds,
                                                 log)
        drv.setup(warm=(i == 0))
        drv.window(args.seconds)
        drv.drain()
        window = drv.results()
        drv.release()
        sides = [("program", None)]
        if args.control and i < args.control_seeds:
            sides.append((f"control.{args.control}", args.control))
        for side, control in sides:
            checks = harness.Checks(cell.limits)
            drv.check(checks, control)
            print(json.dumps({"seed": seed, "side": side,
                              "correct": checks.ok,
                              "attempted": window["attempted"],
                              "failed": window["failed"],
                              "readings": {k: v["value"] for k, v in
                                           checks.as_dict().items()}}),
                  flush=True)
        log(f"seed {seed}: {time.time() - t:.1f} s")
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
