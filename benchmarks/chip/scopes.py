"""Device ops put down to the scopes the program gave them.

A profiler trace names each device op by its HLO instruction and each
program execution by its module (``jit_f(fingerprint)``). The program
keeps, for each jitted entry point it traced, the compiled HLO text of
every signature (``repro.obs.compile.sentinel.hlo_texts``); here that
text is read into the ``jax.named_scope`` path of every instruction
(``hlo_scopes``), and each op in the window goes to the module execution
open at its start, as ``Trace.top_ops`` does, and is looked up in that
module's map.

A program that keeps no such text (an older one) gives ``None``, and
the readers built on this report nothing. Times are unions of op
intervals, so an op nested in another (a fusion inside a ``while``)
counts once; they are summed over one chip's ops, as the cells that read
them run on one chip.
"""

from __future__ import annotations

import bisect
import re
import sys
from typing import Optional

#: how an instruction got its path: its own ``op_name``, else the named
#: instructions of the computations it calls, else its users, else the
#: instruction that calls its computation
SOURCES = ("op_name", "callees", "users", "caller")

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_CALLEES = re.compile(r"\b(?:branch_computations|called_computations)="
                      r"\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")


def _common_path(paths) -> Optional[str]:
    parts = [p.split("/") for p in paths]
    if not parts:
        return None
    head = []
    for level in zip(*parts):
        if any(x != level[0] for x in level):
            break
        head.append(level[0])
    return "/".join(head)


def hlo_scopes(text: str) -> dict:
    """``{instruction name: (scope path, source)}`` for every instruction
    of a compiled module's ``as_text()``, names without their ``%``. The
    scope path is the instruction's ``op_name`` without its last
    component, the primitive (so a ``gather`` scope is not confused with
    the ``gather`` primitive): ``jit(f)/perm.draws/while/body/…``. An
    instruction without ``op_name`` metadata (a copy or wrapped fusion
    the compiler put in) takes the common path of the named instructions
    in the computations it calls; else that of its users; else that of
    the instruction calling its own computation (the empty path in the
    entry computation). ``source`` says which (``SOURCES``)."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            current = comps.setdefault(h[1], [])
            if line.startswith("ENTRY"):
                entry = h[1]
            continue
        i = _INSTR.match(line)
        if i is None or current is None:
            continue
        op = _OP_NAME.search(line)      # scope path / primitive name
        scope = op[1].rpartition("/")[0] if op else None
        callees = _CALLEE.findall(line)
        for group in _CALLEES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")
                        if c.strip()]
        refs = set(_REF.findall(line[i.end():]))
        current.append((i[1], scope, callees, refs))

    named: dict = {}

    def inside(comp):                   # every scope path under ``comp``
        if comp not in named:           # (HLO calls form no cycles)
            named[comp] = [p for _, op, callees, _ in comps.get(comp, ())
                           for p in ([op] if op else [])
                           + [q for c in callees for q in inside(c)]]
        return named[comp]

    out, visited = {}, set()

    def walk(comp, inherited):
        if comp in visited or comp not in comps:
            return
        visited.add(comp)
        own, users = {}, {}
        for name, op, callees, refs in comps[comp]:
            below = _common_path([p for c in callees for p in inside(c)])
            own[name] = ((op, "op_name") if op else
                         (below, "callees") if below else None)
            for r in refs:
                users.setdefault(r, []).append(name)

        def resolve(name):              # users form no cycles either
            if name not in out:
                up = [p for u in users.get(name, ()) if u in own
                      for p in [(own[u] or resolve(u))[0]] if p]
                out[name] = own[name] or ((_common_path(up), "users")
                                          if up else (inherited, "caller"))
            return out[name]

        for name, _, callees, _ in comps[comp]:
            path = resolve(name)[0]
            for c in callees:
                walk(c, path)

    if entry is not None:
        walk(entry, "")
    for comp in comps:                  # computations nothing reaches
        walk(comp, "")
    return out


def merged_scopes(texts) -> dict:
    """``hlo_scopes`` of several compiled texts of one module, a name
    that two of them put under different paths left out."""
    out, clash = {}, set()
    for text in texts:
        for name, found in hlo_scopes(text).items():
            if out.setdefault(name, found)[0] != found[0]:
                clash.add(name)
    for name in clash:
        del out[name]
    return out


def program_scope_map(module: str):
    """``{instruction: (scope path, source)}`` for ``module`` from the
    program's compiled HLO texts, or ``None`` where the program keeps
    none or they cannot be made."""
    try:
        from repro.obs.compile import sentinel
    except ImportError:
        return None
    hlo_texts = getattr(sentinel, "hlo_texts", None)
    if hlo_texts is None:
        return None
    try:
        return merged_scopes(hlo_texts(module)) or None
    except Exception as e:    # a reader must not end the run: say why
        print(f"scopes: no scope map for {module}: {e!r}", file=sys.stderr)
        return None


def module_ops(trace, module: str):
    """``(instruction, start, end)`` of each op of ``module`` in the
    window, the instruction without its ``%``."""
    mods = list(trace._clipped(trace.modules))
    starts = [s for _, s, _ in mods]
    for name, s, e in trace._clipped(trace.ops):
        i = bisect.bisect_right(starts, s) - 1
        if (i >= 0 and s < mods[i][2]
                and mods[i][0].split("(")[0] == module):
            yield name.split(" = ")[0].lstrip("%"), s, e


def union_s(intervals) -> float:
    """Seconds covered by ``(start, end)`` nanosecond intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def seconds_by_scope(trace, module: str, scope_of: dict, scopes):
    """``({scope: seconds}, mapped seconds, op seconds, {source:
    seconds})`` for ``module`` in the window: for each of ``scopes``,
    the union of the ops whose path holds it as a whole component; of
    the ops the map names; of all the module's ops; and for each of
    ``SOURCES``, of the ops the map names that way (an op nested in one
    of another source is counted in both)."""
    ops = list(module_ops(trace, module))
    paths = {name: scope_of[name][0].split("/")
             for name, _, _ in ops if name in scope_of}
    by = {sc: union_s((s, e) for name, s, e in ops
                      if sc in paths.get(name, ()))
          for sc in scopes}
    mapped = union_s((s, e) for name, s, e in ops if name in paths)
    sources = {src: union_s((s, e) for name, s, e in ops
                            if name in paths and scope_of[name][1] == src)
               for src in SOURCES}
    return by, mapped, union_s((s, e) for _, s, e in ops), sources
