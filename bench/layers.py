"""Per-layer readings that the run record does not carry: device time by the
step's named scopes, and window deltas of the trainer's exact counters.

The program runs each layer of its train step under a ``jax.named_scope``
(``SCOPES``; ``repro.obs.tracing.STEP_SCOPES``), so every instruction of the
compiled step carries its scope path in the HLO ``op_name`` metadata, e.g.
``jit(train_step)/transpose(jvp(cache.lookup))/shard.exchange/...``.
``hlo_scopes`` puts each instruction under the innermost listed scope on
that path, matching a scope as a whole path component also inside
``jvp(...)`` / ``transpose(...)``.  A fusion goes by its root, a tuple root
by its operands and a copy-like op without metadata by its operand: the
rules ``trace.hlo_categories`` uses for classes.  Where that finds no scope
(XLA made the root and gave it no metadata), the op goes by the scope most
instructions of the computations it calls carry.  Instructions under no
scope, and ops of other programs, are unscoped (``""``), so the scope times
plus the unscoped time add up to the busy time.

The op -> scope map comes from the compiled step itself: of the process's
live ``jax.stages.Compiled`` programs, the one whose HLO carries the scopes
and whose ops own most of the window's device time.  A program without the scopes gives no map, and its
readers report nothing.
"""
from __future__ import annotations

import gc
import re
from collections import Counter
from typing import Dict, Iterable, List, Mapping, Optional, Tuple

from bench import trace as tr

__all__ = ["SCOPES", "UNSCOPED", "hlo_scopes", "scope_of", "scope_times", "step_scopes",
           "scope_ms_per_step", "window_counter"]

SCOPES = ("cache.plan", "cache.movement", "cache.lookup", "shard.exchange", "dense",
          "cache.row_update", "telemetry")
UNSCOPED = ""
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?!p?jit\()[\w\-]+\((.*)\)$")  # jvp(..), transpose(..), vmap(..)
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_PASS_THROUGH = {"bitcast", "copy", "reshape", "transpose", "convert", "get-tuple-element"}


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` that is a component of the scope path
    ``op_name`` (transform wrappers such as ``jvp(..)`` peeled; a ``jit(..)``
    names a function, never a scope), or ``""``."""
    found = UNSCOPED
    for part in op_name.split("/"):
        while True:
            if part in SCOPES:
                found = part
                break
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return found


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> scope (one of ``SCOPES`` or ``""``) for every
    instruction of every computation in a compiled module's text."""
    comps: Dict[str, Dict[str, Tuple[str, List[str], str]]] = {}
    roots: Dict[str, str] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        head = tr._COMP_HEAD.match(line)
        if head and " = " not in line:
            cur = head.group(1)
            comps[cur] = {}
            continue
        m = tr._INSTR.match(line)
        if m and cur is not None:
            comps[cur][m.group(2)] = tr._split_instr(m.group(3))
            if m.group(1):
                roots[cur] = m.group(2)
    memo: Dict[Tuple[str, str], str] = {}
    inner: Dict[str, Counter] = {}

    def contents(comp: str, depth: int = 0) -> Counter:
        """Scopes of the metadata of every instruction in ``comp`` and in the
        computations it calls."""
        if comp not in inner:
            inner[comp] = Counter()
            for _, _, attrs in comps.get(comp, {}).values():
                m = _OP_NAME.search(attrs)
                if m and scope_of(m.group(1)):
                    inner[comp][scope_of(m.group(1))] += 1
                if depth < 32:
                    for sub in _CALLED.findall(attrs):
                        inner[comp].update(contents(sub, depth + 1))
        return inner[comp]

    def scope(comp: str, name: str, depth: int = 0) -> str:
        key = (comp, name)
        if key in memo:
            return memo[key]
        op, operands, attrs = comps[comp].get(name, ("", [], ""))
        m = _OP_NAME.search(attrs)
        own = scope_of(m.group(1)) if m else UNSCOPED
        out = UNSCOPED
        if depth < 32:
            sub = re.search(r"calls=%?([\w.\-]+)", attrs) if op == "fusion" else None
            if sub and sub.group(1) in comps:
                out = scope(sub.group(1), roots.get(sub.group(1), ""), depth + 1)
            elif not own and op == "tuple":
                got = [scope(comp, o, depth + 1) for o in operands if o in comps[comp]]
                got = [s for s in got if s]
                out = Counter(got).most_common(1)[0][0] if got else UNSCOPED
            elif not own and op in _PASS_THROUGH and operands and operands[0] in comps[comp]:
                out = scope(comp, operands[0], depth + 1)
        out = out or own
        if not out:  # a root without metadata: the scope most of its body carries
            body = Counter()
            for sub in _CALLED.findall(attrs):
                body.update(contents(sub))
            out = body.most_common(1)[0][0] if body else UNSCOPED
        memo[key] = out
        return out

    return {name: scope(comp, name) for comp, instrs in comps.items() for name in instrs}


def scope_times(by_op: Mapping[str, float], scopes: Mapping[str, str]) -> Dict[str, float]:
    """One device's owned time per scope, ``""`` for the unscoped rest; the
    values add up to the sum of ``by_op`` (the busy time)."""
    out = {s: 0.0 for s in SCOPES + (UNSCOPED,)}
    for name, ns in by_op.items():
        out[scopes.get(name, UNSCOPED)] += ns
    return out


def _live_hlo() -> Iterable[str]:
    """The HLO text of every compiled program alive in the process (the
    harness compiles its step with ``.lower(...).compile()``)."""
    import jax

    for obj in gc.get_objects():
        if isinstance(obj, jax.stages.Compiled):
            yield obj.as_text()


_cache: List = [None, None]  # [run record, its scope map]


def step_scopes(run, texts: Optional[Iterable[str]] = None) -> Optional[Dict[str, str]]:
    """The op -> scope map of the step the traced window ran: of the HLO
    ``texts`` (default: the process's live compiled programs) that carry any scope,
    the one whose scoped ops own the most of the window's device time.
    ``None`` when none carries a scope."""
    if texts is None and _cache[0] is run:
        return _cache[1]
    ops: Dict[str, float] = {}
    for w in (run.devices or {}).values():
        for name, ns in w.by_op.items():
            ops[name] = ops.get(name, 0.0) + ns
    best, best_ns = None, -1.0
    for text in (_live_hlo() if texts is None else texts):
        if not any(s in text for s in SCOPES):
            continue
        scopes = hlo_scopes(text)
        if not any(scopes.values()):
            continue
        ns = sum(t for name, t in ops.items() if scopes.get(name))
        if ns > best_ns:
            best, best_ns = scopes, ns
    if texts is None:
        _cache[:] = [run, best]
    return best


def scope_ms_per_step(run, scope: str) -> Optional[float]:
    """Device time under ``scope`` per window step, mean over the chips;
    ``None`` without a trace or without the step's scope map."""
    if not run.devices or not run.window_steps:
        return None
    scopes = step_scopes(run)
    if scopes is None:
        return None
    ns = sum(scope_times(w.by_op, scopes)[scope] for w in run.devices.values())
    return ns / len(run.devices) / 1e6 / run.window_steps


def window_counter(run, key: str) -> Optional[int]:
    """The window's delta of the trainer hub's exact counter ``key``: from the
    history of the run's ``Trainer`` (the one whose ``cache_misses`` delta over
    the last ``window_steps`` records equals the record's ``misses``), its
    record before the window to its last.  ``None`` where no trainer holds
    ``key``."""
    from repro.train.trainer import Trainer

    n = run.window_steps
    for obj in gc.get_objects():
        if not isinstance(obj, Trainer) or len(obj.history) <= n or not n:
            continue
        first, last = obj.history[-n - 1], obj.history[-1]
        if key not in last or key not in first:
            continue
        if run.misses is not None and \
                last.get("cache_misses", 0) - first.get("cache_misses", 0) != run.misses:
            continue
        return last[key] - first[key]
    return None
