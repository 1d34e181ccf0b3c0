"""The harness: one run of one cell, from set-up to the result line.

Everything that belongs to one cell is found by name:
``BENCHMARK.json`` names the cell's configuration and traffic; the
configuration is ``configs/<config>.json``, the traffic mix
``traffic/<traffic>.json`` (its ``driver`` names the general generator
in ``drivers/`` that reads it), the limits of the output check
``limits/<cell>.json``, and each per-layer metric ``metrics/<name>.py``.
Adding a cell adds files; no file here changes.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def process_start() -> float:
    """The process's start on the ``time.time`` clock."""
    try:
        import psutil
        return psutil.Process().create_time()
    except (ImportError, OSError):
        return time.time()


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# finding a cell's pieces by name
# --------------------------------------------------------------------------
def _json(path: Path):
    with open(path) as fh:
        return json.load(fh)


class Cell:
    """One entry of ``workloads`` with its pieces. ``bench`` is the
    benchmark's description, ``BENCHMARK.json`` unless given."""

    def __init__(self, name: str, root: Path = ROOT, bench: dict = None):
        self.bench = bench or _json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = _json(root / configs[self.workload["config"]]["file"])
        self.traffic = _json(HERE / "traffic"
                             / f"{self.workload['traffic']}.json")
        limits = HERE / "limits" / f"{name}.json"
        self.limits = _json(limits)["limits"] if limits.exists() else {}

    def end_to_end(self):
        """The end-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def driver_module(cell: Cell):
    return importlib.import_module(
        f"benchmarks.chip.drivers.{cell.traffic['driver']}")


def metric_reader(name: str):
    """``metrics/<name>.py``: its ``read(trace, facts, peaks)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


# --------------------------------------------------------------------------
# the output check
# --------------------------------------------------------------------------
class Checks:
    """Each compared number beside its limit. A number passes when it is
    at most its limit; NaN, and a number with no limit, fail. Of a
    number read more than once the worst reading stands, and NaN is the
    worst."""

    def __init__(self, limits: dict):
        self.limits = dict(limits)
        self.values: dict = {}

    def add(self, name: str, value) -> None:
        value = float(value)
        old = self.values.get(name)
        if (old is None or math.isnan(value)
                or (not math.isnan(old) and value > old)):
            self.values[name] = value

    def ok_of(self, name) -> bool:
        limit = self.limits.get(name)
        return limit is not None and bool(self.values[name] <= limit)

    @property
    def ok(self) -> bool:
        return bool(self.values) and all(self.ok_of(k) for k in self.values)

    def as_dict(self) -> dict:
        return {k: {"value": v, "limit": self.limits.get(k)}
                for k, v in sorted(self.values.items())}

    def lines(self):
        for k, v in sorted(self.values.items()):
            yield (f"check {k}: value={v!r} limit={self.limits.get(k)!r} "
                   f"{'ok' if self.ok_of(k) else 'FAIL'}")


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------
def annotate(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(logdir: Path, on: bool):
    if not on:
        yield
        return
    import jax
    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache``. Every program is
    kept, however quickly it compiled, so a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (not cache loads) while it is armed."""

    def __init__(self):
        import jax
        self.count, self.armed, self.names = 0, False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.names.append(kwargs.get("fun_name", "?"))


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def devices_for(cell: Cell, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found platform "
                         f"{devices[0].platform!r}")
        if len(devices) < cell.chips:
            raise NoChip(f"{cell.name} needs {cell.chips} chips; JAX "
                         f"found {len(devices)}")
    return devices[:cell.chips]


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, log=None, control=None,
        compile_cache: bool = True) -> dict:
    """One run; returns the result line as a dict.

    The tests drive a run on the CPU with ``require_tpu`` and
    ``compile_cache`` off; ``control`` replaces the program's answers
    with the reference's in that precision before the comparison."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    started = process_start()
    devices = devices_for(cell, require_tpu)
    dev = devices[0]
    peak_row = peaks(dev.device_kind) if require_tpu else None
    cache = enable_compile_cache() if compile_cache else None
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    log(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(trace)}"
        f" | {dev.platform} {dev.device_kind} x{len(devices)} | compile "
        f"cache {cache}")
    counter = CompileCounter()
    drv = driver_module(cell).Driver(cell, seed, seconds, log)
    drv.setup()
    setup_s = time.time() - started
    log(f"setup_s {setup_s!r}")

    logdir = ROOT / ".bench_trace" / cell.name
    counter.armed = True
    with traced(logdir, trace):
        with annotate("bench.window"):
            drv.window(seconds)
    counter.armed = False
    drv.drain()
    window = drv.results()
    log(f"window: {json.dumps(window['metrics'])} attempted "
        f"{window['attempted']} failed {window['failed']} compiles in "
        f"window {counter.count}")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    drv.release()

    checks = Checks(cell.limits)
    drv.check(checks, control)
    metrics, breakdown = {}, None
    if trace:
        from benchmarks.chip.tracefile import Trace
        tr = Trace.from_profile(str(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        for m in cell.per_layer():
            value = metric_reader(m["name"])(tr, window["facts"], peak_row)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            value = (setup_s if m["name"] == "setup_s"
                     else window["metrics"][m["name"]])
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": checks.ok,
              "attempted": int(window["attempted"]),
              "failed": int(window["failed"]),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if counter.count:
        log(f"{counter.count} programs compiled inside the window: "
            f"{sorted(set(counter.names))}")
    result["checks"] = checks.as_dict()
    for line in checks.lines():
        log(line)
    return result
