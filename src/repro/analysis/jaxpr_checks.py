"""Trace-level contract checks (``jax.make_jaxpr`` — nothing executes).

Four invariants per registered entry, driven by its :class:`Contract`:

* **host-transfer** — no ``device_put`` / callback / infeed primitive
  anywhere in the traced body (recursing into sub-jaxprs: pjit, scan, while,
  cond, vmap, custom_vjp, pallas_call).  One of these inside the hot path is
  a synchronous host round-trip per step.
* **f64** — no f64/c128 result and no ``convert_element_type`` to them
  (x64 creep doubles the wire bytes of every host<->device row move).
* **int-counter** — output leaves whose tree path matches the contract's
  ``int_counters`` regexes stay int32/uint32 (the exact-counter contract:
  PR4's telemetry totals and PR5's tracker clock both wrap, never round).
* **sort-bound** — largest ``sort`` operand (along its sort dimension) must
  not exceed ``max_sort_size`` at the smoke shapes; entries declaring
  bounded-top-K set a small bound so a full-capacity argsort fails.

Plus the **retrace** check: abstractly advance the entry's arguments one step
(``SmokeCase.advance`` under ``jax.eval_shape``) and require identical avals
— shape, dtype and weak_type — at step t and t+1.  Any difference means jit
recompiles every step, which silently destroys pipeline overlap.
"""
from __future__ import annotations

import re
from typing import Any, Iterator, List, Tuple

import jax
from jax.extend import core as jax_core
import numpy as np
from jax.api_util import shaped_abstractify

from repro.analysis.contracts import Contract, Violation
from repro.analysis.smoke import SmokeCase

__all__ = [
    "check_case",
    "check_signature_stability",
    "iter_eqns",
    "HOST_TRANSFER_PRIMITIVES",
]

HOST_TRANSFER_PRIMITIVES = frozenset(
    {
        "device_put",
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "infeed",
        "outfeed",
        "host_callback",
        "copy_to_host",
    }
)

_F64 = (np.dtype("float64"), np.dtype("complex128"))
_INT_OK = (np.dtype("int32"), np.dtype("uint32"))


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jax_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax_core.Jaxpr):
                yield x


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every equation of ``jaxpr`` and (recursively) of its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _trace(case: SmokeCase) -> Any:
    return jax.make_jaxpr(case.fn)(*case.args).jaxpr


def _aval_of(var: Any):
    return getattr(var, "aval", None)


def check_host_transfer(case: SmokeCase, c: Contract) -> List[Violation]:
    if not c.no_host_transfer:
        return []
    out = []
    for eqn in iter_eqns(_trace(case)):
        if eqn.primitive.name not in HOST_TRANSFER_PRIMITIVES:
            continue
        # device_put of a scalar LITERAL is trace-time constant placement
        # (e.g. ``jnp.unique(..., fill_value=<int>)``) — XLA folds it; only a
        # device_put of a traced/captured value is a real mid-graph transfer.
        if eqn.primitive.name == "device_put" and all(
            isinstance(v, jax_core.Literal) for v in eqn.invars
        ):
            continue
        out.append(
            Violation(
                "host-transfer",
                c.name,
                f"primitive '{eqn.primitive.name}' in traced body",
            )
        )
    return out


def check_f64(case: SmokeCase, c: Contract) -> List[Violation]:
    if not c.no_f64:
        return []
    out = []
    for eqn in iter_eqns(_trace(case)):
        new_dtype = eqn.params.get("new_dtype")
        if (
            eqn.primitive.name == "convert_element_type"
            and new_dtype is not None
            and np.dtype(new_dtype) in _F64
        ):
            out.append(
                Violation("f64", c.name, f"convert_element_type to {new_dtype}")
            )
            continue
        for var in eqn.outvars:
            aval = _aval_of(var)
            if aval is not None and getattr(aval, "dtype", None) in _F64:
                out.append(
                    Violation(
                        "f64",
                        c.name,
                        f"'{eqn.primitive.name}' produces {aval.dtype}",
                    )
                )
                break
    return out


def check_int_counters(case: SmokeCase, c: Contract) -> List[Violation]:
    if not c.int_counters:
        return []
    out_tree = jax.eval_shape(case.fn, *case.args)
    leaves = jax.tree_util.tree_flatten_with_path(out_tree)[0]
    out = []
    for path, leaf in leaves:
        ps = jax.tree_util.keystr(path)
        for pat in c.int_counters:
            if re.search(pat, ps) and np.dtype(leaf.dtype) not in _INT_OK:
                out.append(
                    Violation(
                        "int-counter",
                        c.name,
                        f"output leaf '{ps}' is {leaf.dtype}, not int32/uint32",
                    )
                )
                break
    return out


def check_sort_bound(case: SmokeCase, c: Contract) -> List[Violation]:
    if c.max_sort_size is None:
        return []
    out = []
    for eqn in iter_eqns(_trace(case)):
        if eqn.primitive.name != "sort":
            continue
        dim = eqn.params.get("dimension", -1)
        sizes = [
            _aval_of(v).shape[dim]
            for v in eqn.invars
            if _aval_of(v) is not None and getattr(_aval_of(v), "shape", ())
        ]
        size = max(sizes, default=0)
        if size > c.max_sort_size:
            out.append(
                Violation(
                    "sort-bound",
                    c.name,
                    f"sort over {size} elements exceeds declared "
                    f"max_sort_size={c.max_sort_size} at smoke shapes",
                )
            )
    return out


def _sig(tree: Any) -> Tuple[Any, List[Tuple[str, Tuple]]]:
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    sig = []
    for path, leaf in leaves:
        aval = shaped_abstractify(leaf)
        sig.append(
            (
                jax.tree_util.keystr(path),
                (tuple(aval.shape), str(aval.dtype), bool(aval.weak_type)),
            )
        )
    return treedef, sig


def check_signature_stability(case: SmokeCase, c: Contract) -> List[Violation]:
    """Re-abstract the entry's args at step t and t+1; any aval difference
    (incl. weak_type) means a per-step retrace."""
    if not c.stable_signature or case.advance is None:
        return []
    td0, sig0 = _sig(case.args)
    nxt = jax.eval_shape(lambda *a: case.advance(*a), *case.args)
    td1, sig1 = _sig(nxt)
    if td0 != td1:
        return [
            Violation(
                "retrace", c.name,
                "argument tree structure changes between step t and t+1",
            )
        ]
    out = []
    for (p0, a0), (_, a1) in zip(sig0, sig1):
        if a0 != a1:
            out.append(
                Violation(
                    "retrace",
                    c.name,
                    f"arg leaf '{p0}' aval drifts {a0} -> {a1} "
                    "(shape, dtype, weak_type)",
                )
            )
    return out


def check_case(case: SmokeCase, c: Contract) -> List[Violation]:
    """All jaxpr-level checks for one entry."""
    out: List[Violation] = []
    try:
        out += check_host_transfer(case, c)
        out += check_f64(case, c)
        out += check_int_counters(case, c)
        out += check_sort_bound(case, c)
        out += check_signature_stability(case, c)
    except Exception as e:  # a case that cannot even trace is itself a finding
        out.append(Violation("trace-error", c.name, f"{type(e).__name__}: {e}"))
    return out
