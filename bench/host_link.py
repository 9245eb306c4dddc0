"""Device time of the host link: the ops the compiled step runs under the
``cache.host_link`` named scope, where a host-resident slow tier's rows cross
between host memory and the device (``repro.store.host_store``).

The scope nests inside ``cache.movement`` and is not one of
``layers.SCOPES``, so this module finds its ops itself, from the compiled
step's HLO text: an instruction is a link op when the scope is a component
of its ``op_name`` path (transform wrappers such as ``jvp(..)`` peeled), or
when it is a fusion whose root is one.  The text is that of the live
compiled program that carries the scope and whose link ops own most of the
window's device time.  A program without the scope (a step whose slow tier
is in HBM, or a program older than the scope) gives nothing to read.
"""
from __future__ import annotations

import gc
import re
from typing import Dict, Iterable, Optional, Set, Tuple

__all__ = ["SCOPE", "in_scope", "link_ops", "host_link_ms_per_step"]

SCOPE = "cache.host_link"
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?!p?jit\()[\w\-]+\((.*)\)$")  # jvp(..), transpose(..)
_FUSION = re.compile(r"\sfusion\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def in_scope(op_name: str) -> bool:
    """True when ``SCOPE`` is a component of the scope path ``op_name``."""
    for part in op_name.split("/"):
        while True:
            if part == SCOPE:
                return True
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return False


def link_ops(text: str) -> Set[str]:
    """Names of the instructions of a compiled module's text that run under
    ``SCOPE``: by their own metadata, a fusion by its root's."""
    comps: Dict[str, Dict[str, str]] = {}
    roots: Dict[str, str] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head and " = " not in line:
            cur = head.group(1)
            comps[cur] = {}
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            comps[cur][m.group(2)] = m.group(3)
            if m.group(1):
                roots[cur] = m.group(2)
    memo: Dict[Tuple[str, str], bool] = {}

    def linked(comp: str, name: str, depth: int = 0) -> bool:
        key = (comp, name)
        if key not in memo:
            rest = comps.get(comp, {}).get(name, "")
            m = _OP_NAME.search(rest)
            out = bool(m and in_scope(m.group(1)))
            sub = _CALLS.search(rest) if _FUSION.search(rest) else None
            if not out and sub and sub.group(1) in comps and depth < 32:
                out = linked(sub.group(1), roots.get(sub.group(1), ""), depth + 1)
            memo[key] = out
        return memo[key]

    return {name for comp, instrs in comps.items() for name in instrs if linked(comp, name)}


def _live_hlo() -> Iterable[str]:
    """The HLO text of every compiled program alive in the process (the
    harness compiles its step with ``.lower(...).compile()``)."""
    import jax

    for obj in gc.get_objects():
        if isinstance(obj, jax.stages.Compiled):
            yield obj.as_text()


def host_link_ms_per_step(run, texts: Optional[Iterable[str]] = None) -> Optional[float]:
    """Device time under ``SCOPE`` per window step, mean over the chips;
    ``None`` without a trace or without a live program that carries the
    scope.  ``texts`` defaults to the process's live compiled programs."""
    if not run.devices or not run.window_steps:
        return None
    ops: Dict[str, float] = {}
    for w in run.devices.values():
        for name, ns in w.by_op.items():
            ops[name] = ops.get(name, 0.0) + ns
    best_ns = None
    for text in (_live_hlo() if texts is None else texts):
        if SCOPE not in text:
            continue
        names = link_ops(text)
        if not names:
            continue
        owned = sum(t for name, t in ops.items() if name in names)
        if best_ns is None or owned > best_ns:
            best_ns = owned
    if best_ns is None:
        return None
    return best_ns / len(run.devices) / 1e6 / run.window_steps
