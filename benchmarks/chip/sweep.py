#!/usr/bin/env python3
"""Find the highest rate a service cell sustains: one process, one
warm-up, then one window per rate on a fresh service.

    python3 benchmarks/chip/sweep.py --workload qiita_mix.open80 \
        --rates 2,3,4,5,6 --seconds 30 --seed 1

For each rate it prints the requests due, the backlog at the window's
close (due and not ended), the completion rate and the latency
quartiles. The knee is the highest rate whose backlog stays near the
service's concurrency (``max_active``) instead of growing with the
window. Needs the chip; the rate it finds goes into the traffic file.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np

    from benchmarks.chip import harness
    cell = harness.Cell(args.workload)
    harness.devices_for(cell, require_tpu=True)
    harness.enable_compile_cache()
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    drv = harness.driver_module(cell).Driver(cell, args.seed, args.seconds,
                                             log)
    t = time.time()
    drv.setup()
    log(f"setup {time.time() - t:.1f} s")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        drv.tr["rate_per_s"] = rate
        drv.plan(np.random.default_rng([args.seed, 10 + i]))
        drv.svc = drv._service(drv.data)
        drv.window(args.seconds)
        backlog = sum(1 for h in drv.handles if not h.done)
        drv.drain()
        res = drv.results()
        print(json.dumps({"rate_per_s": rate, "due": len(drv.due),
                          "backlog_at_close": backlog,
                          "failed": res["failed"],
                          **res["metrics"]}), flush=True)
        del drv.svc
    return 0


if __name__ == "__main__":
    sys.exit(main())
