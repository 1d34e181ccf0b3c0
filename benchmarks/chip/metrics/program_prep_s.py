"""Seconds the process spent preparing programs: jaxpr tracing,
lowering, backend compiles and compile-cache loads, as the program
counts them itself (``repro.obs.compile.sentinel.prep_seconds``, an
event nested in another counted once). A sound window compiles nothing,
so these are set-up's. The counts and seconds by kind go to standard
error. Nothing is read from a program that keeps no such counter."""

import sys


def read(trace, facts, peaks):
    try:
        from repro.obs.compile import sentinel
    except ImportError:
        return None
    prep = getattr(sentinel, "prep", None)
    if prep is None:
        return None
    counted = prep()
    by_entry = counted.get("by_entry", {})
    top = sorted(by_entry.items(), key=lambda kv: -sum(
        v["seconds"] for v in kv[1].values()))[:6]
    print(f"program_prep_s: "
          f"{ {k: v for k, v in counted.items() if k != 'by_entry'}!r}; "
          f"top entries {dict(top)!r}", file=sys.stderr)
    return counted["seconds"] or None
