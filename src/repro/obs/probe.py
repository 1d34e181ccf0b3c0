"""Compile-time measurement of the jitted entry points (the MEASURED half).

Everything else in ``repro.obs`` is analytic: the ledger prices pass
tables, ``tune.model`` prices tiles, and the BENCH gates compare closed
forms against closed forms. This module asks the compiler what a program
*actually* does: ``jit(...).lower(avals).compile()`` and read back

* ``cost_analysis()``  — flops and HLO-level bytes accessed,
* ``memory_analysis()`` — argument/output/temp/alias sizes (peak
  allocation = arg + out + temp − alias),
* ``as_text()``        — the optimized HLO, for the scan correction.

Probes are **ahead-of-time**: operands are ``jax.ShapeDtypeStruct``
avals, so nothing executes and no n²-or-worse buffer is materialized to
measure a program. Records are keyed by the same entry-point names the
``CompileSentinel`` uses (``kernels.permute_reduce``,
``dist.panel_stats``, ``stats.engine.tile``, ``pcoa.fsvd_matfree``, …),
so a ``RunReport``'s ``measured`` section lines up with its ``compile``
section. Probing necessarily traces, so each probe also counts as one
sentinel trace of its entry point — at the session's own geometry the
signature already exists and the program count does not grow.

The scan-body undercount correction (inherited from the retired
``repro.roofline`` module, which established it for collectives):
XLA's ``cost_analysis()`` counts a while-loop body ONCE, but our hot
loops are ``lax.scan``s — ``kernels.permute_reduce`` streams m/chunk
condensed chunks, the ``dist`` production fallback ``lax.map``s row
sub-panels — so the raw figure undercounts the dominant traffic by the
trip count. ``scan_corrected_bytes`` re-adds ``(trips − 1) ×
body_bytes`` per while body, with trip counts taken from XLA's own
``known_trip_count`` backend-config when present (else parsed from the
loop-condition comparison constant) and body bytes summed per top-level
HLO instruction (operands + output as printed; gathers and dynamic
slices count their slice, not their source operand — the same
convention ``HloCostAnalysis`` uses).

These byte counts are HLO-level: every materialized intermediate (index
tensors, gather results) counts, whether or not it stays cache-resident
— so measured bytes sit a documented implementation factor ABOVE the
ledger's streamed-floats floor. ``obs.drift`` owns those factors and the
tolerance bands; this module only measures.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = [
    "ProbeRecord", "probe_lowered", "scan_corrected_bytes",
    "computation_multipliers",
    "probe_permute_reduce", "probe_panel_stats", "probe_pcoa_matfree",
    "probe_statistic", "probe_stream_pass",
    "probe_session", "probe_table", "clear_probe_cache",
]

# --------------------------------------------------------------------------
# HLO text parsing (absorbed from the retired repro.roofline.hlo)
# --------------------------------------------------------------------------
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of every array shape in a (possibly tuple) type."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """{computation name: [instruction lines]} from HLO text."""
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        # computation header lines look like: "%name (args) -> type {"
        if stripped.endswith("{") and ("->" in stripped or
                                       stripped.startswith("ENTRY")):
            m = re.search(r"%?([\w\.\-]+)\s*\(", stripped)
            cur = m.group(1) if m else f"anon{len(comps)}"
            comps[cur] = []
        elif stripped.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(stripped)
    return comps


def _trip_count(cond_lines: List[str]) -> int:
    """While trip count from its condition computation: jax emits
    ``compare(iter, constant(N)), direction=LT``. Max constant wins
    (there may be several; the bound dominates). Fallback 1."""
    consts = []
    for ln in cond_lines:
        if "constant(" in ln and ("s32" in ln or "s64" in ln or
                                  "u32" in ln):
            for m in re.finditer(r"constant\((\d+)\)", ln):
                consts.append(int(m.group(1)))
    return max(consts) if consts else 1


def computation_multipliers(hlo: str) -> Tuple[Dict[str, int], set]:
    """Call-graph execution multipliers per computation.

    Walks while/call/conditional edges from the root computations,
    multiplying into each while body by its trip count — XLA's
    ``"known_trip_count":{"n":...}`` backend-config when annotated, else
    the condition's comparison constant. Returns ``(multipliers,
    while_bodies)`` where ``while_bodies`` is the set of computations
    entered through a while edge (the ones ``cost_analysis()`` counted
    once but the hardware runs ``multiplier`` times).
    """
    comps = _split_computations(hlo)
    calls: Dict[str, List[Tuple[str, str]]] = {c: [] for c in comps}
    whiles: Dict[str, Tuple[str, str]] = {}
    trip_hints: Dict[str, int] = {}
    for cname, lines in comps.items():
        for ln in lines:
            wm = re.search(r"\bwhile\(.*?condition=%?([\w\.\-]+),\s*"
                           r"body=%?([\w\.\-]+)", ln)
            if wm:
                body = wm.group(2)
                calls[cname].append(("while", body))
                whiles[body] = (cname, wm.group(1))
                tm = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', ln)
                if tm:
                    trip_hints[body] = int(tm.group(1))
                continue
            for cm in re.finditer(r"(?:calls|to_apply|body|"
                                  r"branch_computations)"
                                  r"=%?\{?([\w\.\-,\s%]+)\}?", ln):
                for callee in re.split(r"[,\s]+", cm.group(1)):
                    callee = callee.strip().lstrip("%")
                    if callee in comps and callee != cname:
                        calls[cname].append(("call", callee))

    called = {c for lst in calls.values() for _, c in lst}
    roots = [c for c in comps if c not in called]
    mult: Dict[str, int] = {c: 0 for c in comps}
    bodies: set = set()

    def visit(c: str, m: int):
        if m <= 0 or c not in comps:
            return
        mult[c] = mult.get(c, 0) + m
        for kind, callee in calls.get(c, []):
            if kind == "while":
                body = callee
                cond = whiles.get(body, (None, None))[1]
                tc = trip_hints.get(body) or (
                    _trip_count(comps.get(cond, [])) if cond else 1)
                bodies.add(body)
                visit(body, m * tc)
                if cond:
                    visit(cond, m)
            else:
                visit(callee, m)

    for r in roots:
        visit(r, 1)
    return mult, bodies


#: instruction kinds that move no data of their own (aliasing, shape
#: bookkeeping, literals) — excluded from body byte counting
_FREE_OPS = re.compile(
    r"\b(?:parameter|get-tuple-element|tuple|constant|iota|after-all|"
    r"bitcast|copy-start|copy-done|while|conditional|partition-id|"
    r"replica-id)\(")

#: ops whose bytes are the SLICE they touch, not their largest operand —
#: HloCostAnalysis convention (a gather reads output-many elements of
#: its source, not the whole source)
_SLICE_OPS = ("dynamic-update-slice", "dynamic-slice", "gather", "scatter")

#: single-operand aliasing ops an operand may be threaded through before
#: reaching a gather/slice inside a fusion
_ALIAS_OPS = ("bitcast", "copy", "reshape", "transpose")


def _op_output_bytes(seg: str, op: str) -> int:
    """Bytes of the result type printed between '=' and the op token."""
    return _shape_bytes(seg[:seg.find(f" {op}(")])


def _moved_slice_bytes(seg: str, op: str) -> int:
    if op in ("dynamic-update-slice", "scatter"):
        # the moved slice is the smallest array operand printed
        sizes = [s for s in (_shape_bytes(f"{dt}[{dims}]")
                             for dt, dims in _SHAPE_RE.findall(seg))
                 if s > 0]
        return min(sizes) if sizes else 0
    return _op_output_bytes(seg, op)


def _fusion_bytes(line: str, comps: Dict[str, List[str]]) -> int:
    """Boundary traffic of one fusion instruction — the HloCostAnalysis
    convention: root output written once, each operand read in full,
    EXCEPT operands consumed by a gather / dynamic-slice inside the
    fused computation, which are read slice-by-slice and charged the
    total bytes those slicing ops move (a scan body's gather of a
    loop-invariant ``xc`` touches B·chunk elements per iteration, not
    the whole condensed array — counting the printed operand type would
    overcount every iteration by the full array)."""
    cm = re.search(r"calls=%?([\w\.\-]+)", line)
    interior = comps.get(cm.group(1)) if cm else None
    seg = line.split("=", 1)[1]
    out_b = _op_output_bytes(seg, "fusion")
    opseg = seg[seg.find(" fusion(") + len(" fusion("):]
    end = opseg.find("), ")
    opseg = opseg[:end] if end >= 0 else opseg
    operand_bytes = [_shape_bytes(f"{dt}[{dims}]")
                     for dt, dims in _SHAPE_RE.findall(opseg)]
    if not interior:
        return out_b + sum(operand_bytes)
    # interior pass: map %param_i names -> operand position, alias
    # chains, and accumulate sliced-read bytes per operand position
    param_idx: Dict[str, int] = {}
    alias: Dict[str, str] = {}
    for ln in interior:
        pm = re.search(r"%([\w\.\-]+)\s*=\s*[^=]*?\bparameter\((\d+)\)", ln)
        if pm:
            param_idx[pm.group(1)] = int(pm.group(2))
            continue
        for aop in _ALIAS_OPS:
            if f" {aop}(" in ln:
                am = re.search(r"%([\w\.\-]+)\s*=.*?\b" + aop +
                               r"\([^%]*%([\w\.\-]+)", ln)
                if am:
                    alias[am.group(1)] = am.group(2)
                break

    def resolve(name: str) -> Optional[int]:
        for _ in range(8):
            if name in param_idx:
                return param_idx[name]
            if name not in alias:
                return None
            name = alias[name]
        return None

    sliced: Dict[int, int] = {}
    dus_out = 0
    for ln in interior:
        for op in _SLICE_OPS:
            if f" {op}(" not in ln:
                continue
            iseg = ln.split("=", 1)[1] if "=" in ln else ln
            src = re.search(r"\b" + op + r"\([^%]*%([\w\.\-]+)", iseg)
            idx = resolve(src.group(1)) if src else None
            moved = _moved_slice_bytes(iseg, op)
            if op in ("dynamic-update-slice", "scatter"):
                # in-place update: the destination operand aliases the
                # fusion output, so the real traffic is the moved slice
                # (read update + write slot), not the whole buffer
                if idx is not None:
                    sliced[idx] = 0
                dus_out += moved
            elif idx is not None:
                sliced[idx] = sliced.get(idx, 0) + moved
            break
    if dus_out:
        out_b = dus_out
    total = out_b
    for i, b in enumerate(operand_bytes):
        total += sliced[i] if i in sliced else b
    return total


def _instruction_bytes(line: str, comps: Dict[str, List[str]]) -> int:
    """HLO-level bytes accessed by one top-level instruction line:
    operand + output shapes as printed, with fusions charged boundary
    traffic and bare gather/dynamic-slice charged 2× the moved slice."""
    if "=" not in line or _FREE_OPS.search(line):
        return 0
    if " fusion(" in line:
        return _fusion_bytes(line, comps)
    for op in _SLICE_OPS:
        if f" {op}(" in line:
            return 2 * _moved_slice_bytes(line.split("=", 1)[1], op)
    return _shape_bytes(line)


def body_once_bytes(lines: List[str],
                    comps: Dict[str, List[str]]) -> int:
    """One iteration's bytes for a while-body computation."""
    return sum(_instruction_bytes(ln, comps) for ln in lines)


def scan_corrected_bytes(hlo: str, raw_bytes: float) -> Tuple[float, dict]:
    """``raw_bytes`` (the ``cost_analysis()`` figure, while bodies
    counted once) plus ``(trips − 1) × body_bytes`` for every while body
    — the scan-aware correction. Returns ``(corrected, {body: trips})``.
    """
    mult, bodies = computation_multipliers(hlo)
    comps = _split_computations(hlo)
    extra = 0.0
    trips: dict = {}
    for body in bodies:
        m = mult.get(body, 1)
        if m <= 1:
            continue
        once = body_once_bytes(comps.get(body, []), comps)
        extra += (m - 1) * float(once)
        trips[body] = m
    return raw_bytes + extra, trips


# --------------------------------------------------------------------------
# The probe record
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ProbeRecord:
    """One compiled entry point, measured (see module docstring).

    ``bytes_accessed`` is the raw ``cost_analysis()`` figure;
    ``bytes_corrected`` re-adds the while-body trips. ``peak_bytes`` is
    ``argument + output + temp − alias`` from ``memory_analysis()``.
    ``scan_trips`` maps each corrected while body to its trip count
    (empty for scan-free programs, where corrected == raw).
    """

    name: str
    backend: str
    flops: float
    bytes_accessed: float
    bytes_corrected: float
    peak_bytes: int
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    scan_trips: dict
    params: dict

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def probe_lowered(name: str, lowered, params: Optional[dict] = None
                  ) -> ProbeRecord:
    """Compile a ``jax.jit(...).lower(...)`` result and measure it."""
    import jax

    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):          # jax 0.4.x returns [dict]
        cost = cost[0] if cost else {}
    cost = cost or {}
    flops = float(cost.get("flops", 0.0))
    raw = float(cost.get("bytes accessed", 0.0))
    corrected, trips = scan_corrected_bytes(compiled.as_text(), raw)
    mem = compiled.memory_analysis()
    arg = out = temp = alias = 0
    if mem is not None:
        arg = int(mem.argument_size_in_bytes)
        out = int(mem.output_size_in_bytes)
        temp = int(mem.temp_size_in_bytes)
        alias = int(mem.alias_size_in_bytes)
    return ProbeRecord(
        name=name, backend=jax.default_backend(), flops=flops,
        bytes_accessed=raw, bytes_corrected=corrected,
        peak_bytes=arg + out + temp - alias, argument_bytes=arg,
        output_bytes=out, temp_bytes=temp, scan_trips=trips,
        params=dict(params or {}))


#: process-level memo: repeated ``report()`` calls at one geometry
#: compile each probe once (AOT compiles bypass the jit cache)
_MEMO: dict = {}


def clear_probe_cache() -> None:
    _MEMO.clear()


def _memo_key(name: str, params: dict) -> tuple:
    import jax
    return (name, jax.default_backend(),
            tuple(sorted((k, v) for k, v in params.items())))


# --------------------------------------------------------------------------
# Entry-point probes (aval-only: nothing executes)
# --------------------------------------------------------------------------
def probe_permute_reduce(n: int, batch: int = 32, s: int = 1,
                         chunk: Optional[int] = None) -> ProbeRecord:
    """Measure ONE (B, n) tile of the batched condensed reduce — the
    program ``stats.engine``'s ``per_batch`` path runs per tile."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.permute_reduce_ops import (DEFAULT_CHUNK,
                                                  _permute_reduce_jit)

    chunk = DEFAULT_CHUNK if chunk is None else int(chunk)
    params = {"n": n, "batch": batch, "s": s, "chunk": chunk}
    key = _memo_key("kernels.permute_reduce", params)
    if key not in _MEMO:
        m = n * (n - 1) // 2
        f32, i32 = jnp.float32, jnp.int32
        lowered = _permute_reduce_jit.lower(
            jax.ShapeDtypeStruct((m,), f32),
            jax.ShapeDtypeStruct((s, m), f32),
            jax.ShapeDtypeStruct((batch, n), i32),
            jax.ShapeDtypeStruct((m,), i32),
            jax.ShapeDtypeStruct((m,), i32), chunk=chunk)
        _MEMO[key] = probe_lowered("kernels.permute_reduce", lowered,
                                   params)
    return _MEMO[key]


def probe_panel_stats(n: int, d: int, block: int = 256,
                      feature_block: int = 128,
                      metric: str = "braycurtis") -> ProbeRecord:
    """Measure ONE row panel of the distance production sweep (strip +
    fused running sums) — ``dist.driver`` runs ⌈n/block⌉ of these."""
    import jax
    import jax.numpy as jnp

    from repro.dist.driver import _panel_stats
    from repro.dist.metrics import get_metric
    from repro.kernels.dispatch import clamp_block

    b = clamp_block(n, block)
    fb = max(min(feature_block, d), 1)
    params = {"n": n, "d": d, "block": b, "feature_block": fb,
              "metric": metric}
    key = _memo_key("dist.panel_stats", params)
    if key not in _MEMO:
        lowered = _panel_stats.lower(
            jax.ShapeDtypeStruct((b, d), jnp.float32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            metric=get_metric(metric), feature_block=fb)
        _MEMO[key] = probe_lowered("dist.panel_stats", lowered, params)
    return _MEMO[key]


def probe_pcoa_matfree(op, k: int = 10, oversample: int = 10,
                       power_iters: int = 2) -> ProbeRecord:
    """Measure the matrix-free fsvd solve against a (cached) centered-
    Gram operator — the ``pcoa.fsvd_matfree`` entry point."""
    import jax

    from repro.core.pcoa import _randomized_eigh_matfree

    params = {"n": int(op.n), "k": k, "oversample": oversample,
              "power_iters": power_iters}
    key = _memo_key("pcoa.fsvd_matfree", params)
    if key not in _MEMO:
        lowered = _randomized_eigh_matfree.lower(
            op, jax.random.PRNGKey(0), k=k, oversample=oversample,
            power_iters=power_iters)
        _MEMO[key] = probe_lowered("pcoa.fsvd_matfree", lowered, params)
    return _MEMO[key]


def probe_statistic(stat, batch: int = 32) -> Dict[str, ProbeRecord]:
    """Measure one statistic's engine entry points: the hoist program
    (``stats.engine.hoist_and_observe``) and one padded (B, n) tile of
    the per-batch program (``stats.engine.tile``)."""
    import jax
    import jax.numpy as jnp

    from repro.stats import engine

    records = {}
    records["stats.engine.hoist_and_observe"] = probe_lowered(
        "stats.engine.hoist_and_observe",
        engine.hoist_and_observe.lower(stat),
        {"stat": type(stat).__name__, "n": int(stat.n)})
    inv, _ = jax.eval_shape(engine.hoist_and_observe, stat)
    orders = jax.ShapeDtypeStruct((batch, int(stat.n)), jnp.int32)
    records["stats.engine.tile"] = probe_lowered(
        "stats.engine.tile",
        engine.tile_statistics.lower(stat, inv, orders),
        {"stat": type(stat).__name__, "n": int(stat.n), "batch": batch})
    return records


def probe_stream_pass(n: int) -> ProbeRecord:
    """Measure one elementwise fp32 pass over (n,) — the program
    ``tune.budget.calibrate()`` times; its compiled byte count is the
    probe-backed calibration's rate-constant feature."""
    import jax
    import jax.numpy as jnp

    params = {"n": n}
    key = _memo_key("tune.stream_pass", params)
    if key not in _MEMO:
        lowered = jax.jit(lambda a: a * 2.0 + 1.0).lower(
            jax.ShapeDtypeStruct((n,), jnp.float32))
        _MEMO[key] = probe_lowered("tune.stream_pass", lowered, params)
    return _MEMO[key]


# --------------------------------------------------------------------------
# Session-level front door
# --------------------------------------------------------------------------
def probe_session(ws, dimensions: int = 10) -> Dict[str, ProbeRecord]:
    """Measure the entry points a ``Workspace`` session executes, at the
    session's own resolved geometry (so sentinel signatures match and
    the drift sentinel reconciles like-for-like):

    * ``kernels.permute_reduce`` — always (every permutation test);
    * ``dist.panel_stats``       — feature-backed sessions (production);
    * ``pcoa.fsvd_matfree``      — when the operator hoist is already
      cached (probing must not trigger builds mid-report).
    """
    cfg = ws.config
    tiles = ws.resolved_tiles()
    n = ws.n
    records: Dict[str, ProbeRecord] = {}
    records["kernels.permute_reduce"] = probe_permute_reduce(
        n, batch=tiles["batch_size"], s=1, chunk=tiles["chunk"])
    if ws._features is not None:
        records["dist.panel_stats"] = probe_panel_stats(
            n, int(ws._features.shape[1]),
            block=tiles["block"] if isinstance(tiles["block"], int)
            else tiles["block_executed"],
            feature_block=tiles["feature_block_executed"]
            if isinstance(tiles["feature_block_executed"], int) else 128,
            metric=cfg.metric or "braycurtis")
    if "operator" in ws.cache:
        op = ws.cache._store["operator"]      # peek — no counter perturbed
        records["pcoa.fsvd_matfree"] = probe_pcoa_matfree(op, k=dimensions)
    return records


def probe_table(records: Dict[str, ProbeRecord]) -> List[str]:
    """Aligned text rows for one measured section (README / examples)."""
    rows = []
    for name in sorted(records):
        r = records[name]
        scans = (",".join(f"x{v}" for v in r.scan_trips.values())
                 or "-")
        rows.append(f"{name:28s} {r.flops / 1e6:10.2f} Mflop  "
                    f"{r.bytes_corrected / 1e6:10.2f} MB "
                    f"({r.bytes_accessed / 1e6:.2f} raw, scan {scans})  "
                    f"peak {r.peak_bytes / 1e6:8.2f} MB")
    return rows
