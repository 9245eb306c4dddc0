"""Declarative hot-path contract registry.

A jit entry point declares its invariants at the definition site::

    @contract(no_host_transfer=True, donates=("state",), max_sort_size=64)
    def plan_prepare(cfg, state, rows, ...): ...

``@contract`` does NOT wrap the function — zero runtime overhead, no jit
interference — it records a :class:`Contract` in the module-level registry
keyed by ``module.qualname`` and (best effort) tags the callable with
``__contract__``.  The analyzer (``repro.analysis.run``) imports the covered
modules, walks the registry, and traces each entry at the canonical smoke
shapes defined in ``repro.analysis.smoke``.

This module is dependency-light on purpose (stdlib only): ``core``/
``kernels`` import it, never the reverse, so registration can never create an
import cycle.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = ["Contract", "Violation", "contract", "registry", "INT_COUNTERS"]

# The exact-counter contract (PR4/PR5): every telemetry/index leaf the cache
# threads through jit stays int32/uint32 — matched against output tree paths
# (``jax.tree_util.keystr``; registered-dataclass fields render as ``.name``).
INT_COUNTERS: Tuple[str, ...] = (
    r"\.(step|hits|misses|uniq_rows|evictions|uniq_overflows|last_used|use_count"
    r"|slot_to_row|row_to_slot|last_touch|refresh_swaps|refresh_rows"
    r"|routed_lanes|tier_promotions|tier_demotions)$",
)


@dataclasses.dataclass(frozen=True)
class Contract:
    """Machine-checked invariants of one jit entry point.

    ``max_sort_size`` is quoted at the canonical smoke shapes of
    ``analysis.smoke`` (it bounds the largest sort/argsort operand the traced
    body may contain there) — an entry declaring bounded-top-K sets it to a
    small multiple of its per-step unique count, so a full-capacity argsort
    trips the check.  ``int_counters`` are regexes matched against output
    tree paths (``jax.tree_util.keystr``); matching leaves must stay
    int32/uint32 (the exact-counter contract).  ``donates`` names arguments
    the caller is expected to donate; the HLO pass lowers with that donation
    and verifies XLA actually aliased the large buffers (no double-buffered
    arena).
    """

    name: str  # "module.qualname" registry key
    no_host_transfer: bool = True
    no_f64: bool = True
    donates: Tuple[str, ...] = ()
    int_counters: Tuple[str, ...] = ()
    max_sort_size: Optional[int] = None
    stable_signature: bool = True


@dataclasses.dataclass(frozen=True)
class Violation:
    """One finding, from any pass.  ``(check, entry)`` is the baseline key —
    ``detail`` may drift between jax versions without invalidating a
    known-issue entry."""

    check: str  # "host-transfer" | "f64" | "int-counter" | "sort-bound" | ...
    entry: str  # registry key, or "path:line" for AST findings
    detail: str

    @property
    def key(self) -> str:
        return f"{self.check}::{self.entry}"

    def to_dict(self) -> Dict[str, str]:
        return {"check": self.check, "entry": self.entry, "detail": self.detail}


_REGISTRY: Dict[str, Tuple[Callable, Contract]] = {}


def registry() -> Dict[str, Tuple[Callable, Contract]]:
    """Snapshot of every registered entry point: key -> (callable, contract).
    Populated as covered modules are imported (``analysis.smoke`` imports
    them all)."""
    return dict(_REGISTRY)


def contract(
    *,
    no_host_transfer: bool = True,
    no_f64: bool = True,
    donates: Tuple[str, ...] = (),
    int_counters: Tuple[str, ...] = (),
    max_sort_size: Optional[int] = None,
    stable_signature: bool = True,
    name: Optional[str] = None,
) -> Callable:
    """Register the decorated callable's hot-path contract (see module doc).

    Stack ABOVE ``jax.jit`` so the registry holds the jitted callable.  For
    methods the registry key is ``module.Class.method``; ``name`` overrides
    when the qualname would be ambiguous (lambdas, factories).
    """

    def deco(fn: Callable) -> Callable:
        qual = name or f"{fn.__module__}.{getattr(fn, '__qualname__', fn.__name__)}"
        c = Contract(
            name=qual,
            no_host_transfer=no_host_transfer,
            no_f64=no_f64,
            donates=tuple(donates),
            int_counters=tuple(int_counters),
            max_sort_size=max_sort_size,
            stable_signature=stable_signature,
        )
        _REGISTRY[qual] = (fn, c)
        try:
            fn.__contract__ = c
        except (AttributeError, TypeError):  # C++ jit wrappers may refuse
            pass
        return fn

    return deco
