"""Reduction of a profiler trace to per-layer numbers.

Stage 1 (``load``) reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain records: per device the ops of its op line, and the host's spans.
Stage 2 (``reduce_device``) sweeps one device's ops over a window: at every
instant the innermost running op owns the time, so ``busy`` is the union of
the op intervals and the per-category times (plus ``other``) add up to it
exactly.  Ops are put in categories from the compiled step's HLO text
(``hlo_categories``): by opcode, a fusion by its root, a tuple root by its
operands, an output fusion by the op it fuses its epilogue onto.
"""
from __future__ import annotations

import dataclasses
import heapq
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

__all__ = ["Span", "Trace", "load", "hlo_categories", "categorize", "reduce_device",
           "host_activity", "CATEGORIES", "DeviceWindow"]

CATEGORIES = ("sort", "gather_scatter", "matmul", "collective")
_COLLECTIVE = {"all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast", "ragged-all-to-all"}
_BY_OPCODE = {
    **{op: "collective" for base in _COLLECTIVE for op in (base, base + "-start", base + "-done")},
    "sort": "sort",
    "gather": "gather_scatter",
    "scatter": "gather_scatter",
    "dynamic-slice": "gather_scatter",
    "dynamic-update-slice": "gather_scatter",
    "dot": "matmul",
    "convolution": "matmul",
}
_PASS_THROUGH = {"bitcast", "copy", "reshape", "transpose", "convert", "get-tuple-element"}
_PRIORITY = {c: i for i, c in enumerate(("collective", "sort", "gather_scatter", "matmul", "other"))}
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns, on the trace's clock
    end: float
    module: str = ""  # device ops: the HLO module; host spans: the thread


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Span]]  # device id -> its ops
    host: List[Span]  # host spans of every thread


def _stat(event, key: str) -> str:
    for k, v in list(event.stats):
        if k == key:
            return str(v)
    return ""


def _op_name(event_name: str) -> str:
    """The TPU op line names an event by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``); keep the instruction's name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` (needs only JAX)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Span]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: {sorted(lines)}")
            devices[int(m.group(1))] = [
                Span(_op_name(e.name), e.start_ns, e.start_ns + e.duration_ns,
                     _stat(e, "hlo_module"))
                for e in lines[OPS_LINE].events if e.duration_ns > 0]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns, ln.name)
                         for e in ln.events if e.duration_ns > 0]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}: "
                         f"{[p.name for p in pd.planes]}")
    return Trace(devices=devices, host=host)


# ---------------------------------------------------------------------------
# HLO text -> op categories

_COMP_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


def _split_instr(rest: str) -> Tuple[str, List[str], str]:
    """``<shape> <opcode>(<operands>), <attrs>`` -> (opcode, operands, attrs)."""
    i = 0
    if rest.startswith("("):  # tuple shape: skip the balanced parentheses
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        while i < len(rest) and not rest[i].isspace():
            i += 1
    m = re.match(r"\s*([a-z][a-z0-9\-]*)\(", rest[i:])
    if not m:
        return "", [], ""
    j = i + m.end()
    depth, k = 1, j
    while k < len(rest) and depth:
        depth += rest[k] == "("
        depth -= rest[k] == ")"
        k += 1
    # operands print as "<shape> %name" or "name"; shapes hold commas, so keep
    # the tokens that can be names
    toks = [t for t in re.split(r"[\s,]+", rest[j:k - 1]) if t]
    named = [t[1:] for t in toks if t.startswith("%")]
    operands = named or [t for t in toks if re.fullmatch(r"[A-Za-z_][\w.\-]*", t)]
    return m.group(1), operands, rest[k:]


def hlo_categories(text: str) -> Dict[str, str]:
    """Instruction name -> category (``CATEGORIES`` or ``other``) for every
    instruction of every computation in a compiled module's text."""
    comps: Dict[str, Dict[str, Tuple[str, List[str], str]]] = {}
    roots: Dict[str, str] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        head = _COMP_HEAD.match(line)
        if head and " = " not in line:
            cur = head.group(1)
            comps[cur] = {}
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            comps[cur][m.group(2)] = _split_instr(m.group(3))
            if m.group(1):
                roots[cur] = m.group(2)
    memo: Dict[Tuple[str, str], str] = {}

    def called(attrs: str) -> Optional[str]:
        m = re.search(r"calls=%?([\w.\-]+)", attrs)
        return m.group(1) if m else None

    def contents(comp: str) -> Iterable[str]:
        for op, _, attrs in comps.get(comp, {}).values():
            sub = called(attrs) if op == "fusion" else None
            if sub:
                yield from contents(sub)
            else:
                yield _BY_OPCODE.get(op, "other")

    def klass(comp: str, name: str, depth: int = 0) -> str:
        key = (comp, name)
        if key in memo:
            return memo[key]
        op, operands, attrs = comps[comp].get(name, ("", [], ""))
        out = _BY_OPCODE.get(op, "other")
        if depth < 32:
            if op == "fusion" and called(attrs) in comps:
                sub = called(attrs)
                out = klass(sub, roots.get(sub, ""), depth + 1)
                if out == "other" and "kind=kOutput" in attrs:
                    out = min(contents(sub), key=_PRIORITY.get)
            elif op == "tuple":
                cls = [klass(comp, o, depth + 1) for o in operands if o in comps[comp]]
                out = min(cls, key=_PRIORITY.get) if cls else "other"
            elif op in _PASS_THROUGH and operands and operands[0] in comps[comp]:
                out = klass(comp, operands[0], depth + 1)
        memo[key] = out
        return out

    return {name: klass(comp, name) for comp, instrs in comps.items() for name in instrs}


def categorize(op: Span, cats: Mapping[str, str], module_prefix: str) -> str:
    """An op's category: from ``cats`` when it belongs to the categorised
    module, ``other`` for any other module's ops."""
    if op.module and not op.module.startswith(module_prefix):
        return "other"
    return cats.get(op.name, "other")


# ---------------------------------------------------------------------------
# sweep


@dataclasses.dataclass
class DeviceWindow:
    busy_ns: float
    window_ns: float
    by_category: Dict[str, float]  # CATEGORIES + "other"; sums to busy_ns
    by_op: Dict[str, float]  # "<op name>" -> owned ns
    gaps: List[Tuple[float, float]]  # idle intervals inside the window


def reduce_device(ops: Sequence[Span], w0: float, w1: float, cats: Mapping[str, str],
                  module_prefix: str) -> DeviceWindow:
    """Sweep one device's ops over ``[w0, w1]``; each instant belongs to the
    innermost (latest-started) running op."""
    bounds = sorted({w0, w1} | {t for o in ops for t in (o.start, o.end) if w0 < t < w1})
    order = sorted((o for o in ops if o.end > w0 and o.start < w1), key=lambda o: o.start)
    by_cat: Dict[str, float] = defaultdict(float)
    by_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    heap: List[Tuple[float, float, int]] = []  # (-start, end, index)
    nxt = 0
    busy = 0.0
    for a, b in zip(bounds[:-1], bounds[1:]):
        while nxt < len(order) and order[nxt].start <= a:
            heapq.heappush(heap, (-order[nxt].start, order[nxt].end, nxt))
            nxt += 1
        # innermost op still running over [a, b]; ops that ended are dropped
        # lazily, and an enclosing op that ended before a child cannot
        # reappear because its end is a boundary
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        live = [h for h in heap if h[1] > a]
        if not live:
            if gaps and gaps[-1][1] == a:
                gaps[-1] = (gaps[-1][0], b)
            else:
                gaps.append((a, b))
            continue
        op = order[min(live)[2]]
        dt = b - a
        busy += dt
        by_cat[categorize(op, cats, module_prefix)] += dt
        by_op[op.name] += dt
    for c in CATEGORIES + ("other",):
        by_cat.setdefault(c, 0.0)
    return DeviceWindow(busy, w1 - w0, dict(by_cat), dict(by_op), gaps)


def host_activity(host: Sequence[Span], times: Sequence[float], thread: str) -> List[str]:
    """For each instant of ``times``, the innermost span of host thread
    ``thread`` running then (``none`` when it ran none).  One thread's spans
    nest, so a stack swept in time order finds each in O(log n)."""
    spans = sorted((s for s in host if s.module == thread), key=lambda s: (s.start, -s.end))
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = ["none"] * len(times)
    stack: List[Span] = []
    nxt = 0
    for i in order:
        t = times[i]
        while nxt < len(spans) and spans[nxt].start <= t:
            while stack and stack[-1].end <= spans[nxt].start:
                stack.pop()
            stack.append(spans[nxt])
            nxt += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        if stack:
            out[i] = stack[-1].name
    return out
