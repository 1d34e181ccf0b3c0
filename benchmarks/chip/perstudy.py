"""Device time under one scope of one program, per study.

A traced window can be cut partway: the profiler may keep the device ops
of only the first part of a window, less than one study where a study
runs many small ops (UniFrac's production). So a reading here is built
from the module's executions that end before the last kept op (each
whole in the trace) and from how often the program ran them: for each
compiled program of the module (one per signature, such as a table's
width), the mean over its whole executions times its executions a
study, summed over the programs. The executions come from the program's
own count (``sentinel.runs``), taken by the driver around the window
(``runs`` and ``per_study`` here) and handed over as the run's facts.

A module's programs name their instructions alike but mean different
things by them: a map merged over them loses the names they disagree on
(``scopes.merged_scopes``). Here each execution of the module is read
with the map of its own program: the compiled program whose
instructions, by name and result type, match most of the execution's
ops (a trace names an op by its HLO text). The counts, the coverage and
what was read go to standard error.
"""

from __future__ import annotations

import bisect
import re
import sys
import time

from benchmarks.chip import scopes
from benchmarks.chip.tracefile import Trace

#: an instruction's name and result type, in HLO text or a trace's op name
_KEY = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) [\w\-]+\(")


def _sentinel():
    try:
        from repro.obs.compile import sentinel
    except ImportError:
        return None
    return sentinel


def runs(modules) -> dict:
    """{module: {signature: executions so far}}, as the program counts
    them; empty for a program that counts none."""
    sentinel = _sentinel()
    count = getattr(sentinel, "runs", None)
    return {} if count is None else {m: count(m) for m in modules}


def per_study(before: dict, after: dict, studies: int) -> dict:
    """{module: {signature: executions a study}} between two ``runs``
    around a window of ``studies`` whole studies."""
    if not studies:
        return {}
    return {m: {sig: (n - before.get(m, {}).get(sig, 0)) / studies
                for sig, n in now.items()
                if n > before.get(m, {}).get(sig, 0)}
            for m, now in after.items()}


def program_maps(module: str, scope: str, reader: str):
    """{signature: {(instruction, result type): under scope}}, one per
    compiled program of ``module`` that the program kept, or None."""
    compiled = getattr(_sentinel(), "compiled", None)
    if compiled is None:
        return None
    t = time.perf_counter()
    try:
        texts = compiled(module, scope)
    except Exception as e:    # a reader must not end the run: say why
        print(f"{reader}: no scope map for {module}: {e!r}", file=sys.stderr)
        return None
    maps = {}
    for signature, text in texts.items():
        paths = scopes.hlo_scopes(text)
        keys = (_KEY.match(line) for line in text.splitlines())
        maps[signature] = {(k[1], k[2]): scope in paths[k[1]][0].split("/")
                           for k in keys if k and k[1] in paths}
    print(f"{reader}: {len(maps)} scope maps of {module} in "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    return maps or None


def _executions(trace: Trace, module: str, maps: dict):
    """{signature: seconds under the scope of each whole execution of
    that program}, over the executions of ``module`` that end by the
    last kept op; and the seconds of all those executions' ops and of
    the ops a map names."""
    last = max((s + d for _, s, d in trace.ops), default=trace.window[0])
    mods = [m for m in trace._clipped(trace.modules)
            if m[0].split("(")[0] == module and m[2] <= last]
    starts = [s for _, s, _ in mods]
    ops = [[] for _ in mods]
    for name, s, e in trace._clipped(trace.ops):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][2]:
            k = _KEY.match(name)
            ops[i].append(((k[1], k[2]) if k else None, s, e))
    inside, named, every = {}, [], []
    for run in ops:
        sig = max(maps, key=lambda g: sum(k in maps[g] for k, _, _ in run))
        best = maps[sig]
        inside.setdefault(sig, []).append(
            scopes.union_s((s, e) for k, s, e in run if best.get(k)))
        named += [(s, e) for k, s, e in run if k in best]
        every += [(s, e) for _, s, e in run]
    return inside, scopes.union_s(named), scopes.union_s(every)


def scope_seconds(trace: Trace, module: str, scope: str, reader: str,
                  executions):
    """Device seconds a study spends in the ops that ``module``'s
    compiled programs put under ``scope``: per program, the mean of its
    whole executions in the trace times ``executions[signature]``, its
    executions a study. None where the program keeps no scope map of
    ``module``, no program holds ``scope``, the run counted no
    executions, or a program that ran has no whole execution in the
    trace."""
    maps = program_maps(module, scope, reader)
    if maps is None:
        print(f"{reader}: no scope map of {module}; nothing read",
              file=sys.stderr)
        return None
    if not any(any(m.values()) for m in maps.values()):
        print(f"{reader}: the compiled {module} carries no {scope} scope; "
              f"nothing read", file=sys.stderr)
        return None
    if not executions:
        print(f"{reader}: the run counted no executions of {module}; "
              f"nothing read", file=sys.stderr)
        return None
    inside, named, every = _executions(trace, module, maps)
    total = 0.0
    for signature, count in executions.items():
        kept = inside.get(signature)
        print(f"{reader}: {module} {signature}: {count!r} executions a "
              f"study, {len(kept or [])} whole in the trace, under {scope} "
              f"{sum(kept or [])!r} s", file=sys.stderr)
        if not kept:
            print(f"{reader}: no whole execution of that program was "
                  f"kept; nothing read", file=sys.stderr)
            return None
        total += sum(kept) / len(kept) * count
    print(f"{reader}: {len(maps)} programs of {module}; the whole "
          f"executions' ops {every!r} s, named {named!r} s",
          file=sys.stderr)
    return total if total > 0 else None
