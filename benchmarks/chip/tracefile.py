"""From a profiler trace to the few event lists the metric readers use.

``Trace`` holds, in nanoseconds on the profiler's clock:

* ``ops``: the device's operations, ``(name, start, duration)``, from
  the ``XLA Ops`` line of every TPU plane;
* ``modules``: the device's programs, from the ``XLA Modules`` line (a
  jitted function ``f`` runs as the module ``jit_f``);
* ``spans``: the benchmark's own host spans (``bench.*``), written with
  ``jax.profiler.TraceAnnotation``;
* ``window``: the traced window, the ``bench.window`` span.

``Trace.to_json``/``from_json`` keep the same lists in a small file, so
the reduction can be checked against a recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import heapq
import json
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


class Trace:
    def __init__(self, ops, modules, spans, window, devices=1):
        self.ops = sorted(ops, key=lambda e: e[1])
        self.modules = sorted(modules, key=lambda e: e[1])
        self.spans = sorted(spans, key=lambda e: e[1])
        self.window = tuple(window)
        self.devices = max(int(devices), 1)

    # -- loading -------------------------------------------------------------
    @classmethod
    def from_profile(cls, logdir: str) -> "Trace":
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {logdir}")
        data = ProfileData.from_file(files[-1])
        ops, modules, spans, devices = [], [], [], 0
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                devices += 1
                for line in plane.lines:
                    sink = {"XLA Ops": ops,
                            "XLA Modules": modules}.get(line.name)
                    if sink is None:
                        continue
                    sink.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend((e.name, e.start_ns, e.duration_ns)
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX))
        windows = [s for s in spans if s[0] == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        w = max(windows, key=lambda s: s[2])
        return cls(ops, modules, spans, (w[1], w[1] + w[2]), devices)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as fh:
            d = json.load(fh)
        return cls(d["ops"], d["modules"], d["spans"], d["window"],
                   d.get("devices", 1))

    def to_json(self, path: str) -> None:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as fh:
            json.dump({"ops": self.ops, "modules": self.modules,
                       "spans": self.spans, "window": list(self.window),
                       "devices": self.devices}, fh)

    # -- reductions ----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clipped(self, events):
        lo, hi = self.window
        for name, start, dur in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                yield name, s, e

    def busy_intervals(self):
        """The union of device operation intervals inside the window,
        merged, as (start, end) pairs; where a plane has no op line, its
        modules stand in."""
        events = self.ops if self.ops else self.modules
        merged = []
        for _, s, e in sorted(self._clipped(events), key=lambda t: t[1]):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(e - s for s, e in self.busy_intervals()) / 1e9 \
            / self.devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def module_events(self, pattern: str):
        """Module executions inside the window whose name matches
        ``pattern`` (a regular expression searched in the name)."""
        rx = re.compile(pattern)
        return [(n, s, e) for n, s, e in self._clipped(self.modules)
                if rx.search(n)]

    def module_s(self, pattern: str) -> float:
        return sum(e - s for _, s, e in self.module_events(pattern)) / 1e9

    def top_ops(self, count: int = 10):
        """The device operations that took most time, summed by the
        operation's short name within its program (``jit_f/%fusion.2``;
        an op's full name is its HLO text)."""
        mods = list(self._clipped(self.modules))
        starts = [s for _, s, _ in mods]
        total = {}
        for name, s, e in self._clipped(self.ops or self.modules):
            i = bisect.bisect_right(starts, s) - 1
            prog = (mods[i][0].split("(")[0]
                    if i >= 0 and s < mods[i][2] else "?")
            key = f"{prog}/{name.split(' = ')[0]}"
            total[key] = total.get(key, 0) + (e - s)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:count]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, count: int = 10):
        """Idle time on the device, summed by the innermost benchmark
        span open on the host at the middle of each gap."""
        lo, hi = self.window
        edges = [lo] + [x for iv in self.busy_intervals() for x in iv] + [hi]
        spans = sorted((s, s + d, n) for n, s, d in self.spans
                       if n != WINDOW_SPAN)
        total, gaps = {}, {}
        open_, i = [], 0          # heap of (end, start, name) open at mid
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            while i < len(spans) and spans[i][0] <= mid:
                heapq.heappush(open_, (spans[i][1], spans[i][0],
                                       spans[i][2]))
                i += 1
            while open_ and open_[0][0] <= mid:
                heapq.heappop(open_)
            name = (min(open_, key=lambda sp: sp[0] - sp[1])[2]
                    if open_ else "no benchmark span")
            total[name] = total.get(name, 0) + (e - s)
            gaps[name] = gaps.get(name, 0) + 1
        top = sorted(total.items(), key=lambda kv: -kv[1])[:count]
        return [[f"{name} ({gaps[name]} gaps)", ns / 1e9]
                for name, ns in top]
