"""Structured metrics hub: typed instruments, exact-int accumulation, JSONL.

Every int32 counter the cache threads through jit — hits, misses, routed
exchange lanes, host rows moved, refresh swaps — is CUMULATIVE device state
that (a) wraps past 2^31 on long runs (x64 is off) and (b) only becomes a
trustworthy Python int through modulo-2^32 delta accumulation host-side.
:class:`ExactCounter` is the one implementation of that wrap-safe pattern
(the trainer, the serve engine and the benchmarks all use it), and
:meth:`MetricsHub.observe_embedding_metrics`
is the ONE place that knows which families a ``collection.metrics`` dict
carries and how each reconstructs (per-slab counts, optionally priced by a
static per-unit byte size).

The hub also owns the run's JSONL sink.  Records are written with sorted
keys and every wall-clock-dependent field (timestamps, step durations, span
times) quarantined under the reserved ``"wall"`` key, so two identical runs
emit BYTE-IDENTICAL files modulo that one subtree — determinism you can test
(``tests/test_obs.py`` does), which turns telemetry diffs into regression
signals instead of noise.

Dependency-light on purpose: stdlib + jax only (``jax.device_get`` to fetch
counter leaves).  ``core``/``train``/``serve`` import this module, never the
reverse, so the hub can sit under all of them.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, IO, Mapping, Optional, Union

import jax

from repro.obs.hist import FixedHistogram

__all__ = ["ExactCounter", "Gauge", "MetricsHub"]

_WRAP = 1 << 32


def _as_int_map(value: Any) -> Dict[str, int]:
    """Normalize a cumulative observation — scalar, array scalar, or per-key
    mapping of either — to ``{key: int}`` (single scalars key as "")."""
    if isinstance(value, Mapping):
        fetched = jax.device_get(dict(value))
        return {k: int(v) for k, v in fetched.items()}
    return {"": int(jax.device_get(value))}


class ExactCounter:
    """Wrap-free exact totals over cumulative int32 device counters.

    Two ways to feed it:

    * :meth:`add` — a direct host-side increment (already an exact int).
    * :meth:`observe` — an observation of a CUMULATIVE device counter (or a
      per-slab mapping of them).  The per-interval delta is recovered modulo
      2^32 — exact whenever fewer than 2^31 events happen between
      observations, which one step can never exceed — and summed in Python
      integers.  With ``unit`` (an int, or a per-key mapping of ints), each
      key's delta is multiplied by its unit BEFORE summing, so byte totals
      (rows x encoded row size) are wrap-safe too — unlike a one-shot
      product of the raw counter, which inherits its int32 wrap.

    Idempotent under repeated observation of the same values (delta 0), so
    summaries may call it freely.  Totals count from the first observation's
    raw value — exact for fresh states; a state restored with an
    already-wrapped counter under-reports only the pre-restore portion.
    """

    def __init__(self, name: str = ""):
        self.name = name
        self._prev: Dict[str, int] = {}
        self._total = 0

    def add(self, n: int) -> int:
        self._total += int(n)
        return self._total

    def observe(
        self,
        cumulative: Any,
        unit: Optional[Union[int, Mapping[str, Any]]] = None,
    ) -> int:
        cur = _as_int_map(cumulative)
        units: Optional[Dict[str, int]] = None
        if unit is not None:
            units = (
                _as_int_map(unit)
                if isinstance(unit, Mapping)
                else {k: int(unit) for k in cur}
            )
        for k, v in cur.items():
            delta = (v - self._prev.get(k, 0)) % _WRAP
            self._prev[k] = v
            self._total += delta * (units[k] if units is not None else 1)
        return self._total

    @property
    def value(self) -> int:
        return self._total

    # back-compat spelling used by the pre-hub pattern
    total = value


class Gauge:
    """Last-value instrument (floats: hit rate, imbalance, loss)."""

    def __init__(self, name: str = ""):
        self.name = name
        self.value: float = 0.0

    def set(self, v: float) -> float:
        self.value = float(v)
        return self.value


# -- the one registry of cumulative families in a collection metrics dict ---
#
# (record_key, counts_key, unit_key) — counts_key holds per-slab cumulative
# int32 counts; unit_key (None = 1) holds the matching static per-unit byte
# sizes.  Everything the trainer/serve summaries report as exact ints flows
# through this table and nowhere else.
_CUMULATIVE_FAMILIES = (
    ("cache_hits", "slab_hits", None),
    ("cache_misses", "slab_misses", None),
    ("cache_uniq_rows", "slab_uniq_rows", None),
    ("cache_move_rounds", "slab_move_rounds", None),
    ("host_moved_rows", "host_moved_rows", None),
    ("host_wire_bytes", "host_moved_rows", "host_row_bytes"),
    ("exchange_routed_lanes", "exchange_routed_lanes", None),
    ("exchange_bytes", "exchange_routed_lanes", "exchange_lane_bytes"),
    ("exchange_id_bytes", "exchange_routed_lanes", "exchange_id_lane_bytes"),
    ("exchange_row_bytes", "exchange_routed_lanes", "exchange_row_lane_bytes"),
    ("refresh_swaps_exact", "slab_refresh_swaps", None),
    ("refresh_rows_moved_exact", "slab_refresh_rows", None),
    ("slab_tier_promotions", "slab_tier_promotions", None),
    ("slab_tier_demotions", "slab_tier_demotions", None),
)


class MetricsHub:
    """Typed counter/gauge/histogram registry + per-run JSONL sink.

    ``run_dir=None`` gives a sink-less hub: instruments still accumulate
    (the trainer always routes its exact counters through one), ``log`` is a
    no-op.  With a directory, records stream to ``<run_dir>/<run>.jsonl``
    and ``close()`` finalizes the file.

    Snapshot/delta semantics: :meth:`snapshot` captures every instrument's
    current value; :meth:`delta` subtracts a previous snapshot's counters —
    how a serve summary reports per-interval rates off the same hub a
    trainer fills.
    """

    def __init__(
        self,
        run_dir: Optional[str] = None,
        run: str = "run",
        timestamps: bool = True,
    ):
        self.run = run
        self.timestamps = timestamps
        self.jsonl_path: Optional[str] = None
        self._sink: Optional[IO[str]] = None
        self._counters: Dict[str, ExactCounter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, FixedHistogram] = {}
        if run_dir is not None:
            os.makedirs(run_dir, exist_ok=True)
            self.jsonl_path = os.path.join(run_dir, f"{run}.jsonl")
            self._sink = open(self.jsonl_path, "w")
            self.log("meta", {"run": run, "argv": list(sys.argv[1:])})

    # -- typed instruments ---------------------------------------------------

    def counter(self, name: str) -> ExactCounter:
        if name not in self._counters:
            self._counters[name] = ExactCounter(name)
        return self._counters[name]

    def gauge(self, name: str) -> Gauge:
        if name not in self._gauges:
            self._gauges[name] = Gauge(name)
        return self._gauges[name]

    def histogram(
        self, name: str, bounds: Optional[tuple] = None
    ) -> FixedHistogram:
        if name not in self._hists:
            self._hists[name] = (
                FixedHistogram(bounds=bounds)
                if bounds is not None
                else FixedHistogram.latency()
            )
        return self._hists[name]

    # -- the ONE cumulative-counter reconstruction point ---------------------

    def observe_embedding_metrics(self, metrics: Mapping[str, Any]) -> Dict[str, int]:
        """Feed one observation of a ``collection.metrics`` dict; returns the
        exact-int record for the families present (wrap-safe Python ints).

        Add a counter family to ``_CUMULATIVE_FAMILIES`` and every
        consumer (trainer, serve engine, benchmarks) reports it.  Derived
        ``hit_rate_exact`` rides along whenever both hit families exist.

        One ``jax.device_get`` for the whole observation: the per-slab
        counter leaves are fetched as a single tree, not one sync per leaf.
        """
        wanted = {
            key
            for _, counts_key, unit_key in _CUMULATIVE_FAMILIES
            for key in (counts_key, unit_key)
            if key is not None and key in metrics
        }
        fetched = jax.device_get(
            {
                k: dict(metrics[k]) if isinstance(metrics[k], Mapping) else metrics[k]
                for k in wanted
            }
        )
        out: Dict[str, int] = {}
        for record_key, counts_key, unit_key in _CUMULATIVE_FAMILIES:
            if counts_key not in fetched:
                continue
            if unit_key is not None and unit_key not in fetched:
                continue
            unit = fetched[unit_key] if unit_key is not None else None
            out[record_key] = self.counter(record_key).observe(
                fetched[counts_key], unit=unit
            )
        if "cache_hits" in out and "cache_misses" in out:
            h, m = out["cache_hits"], out["cache_misses"]
            out["hit_rate_exact"] = h / max(h + m, 1)
        return out

    # -- JSONL sink ----------------------------------------------------------

    def log(
        self,
        kind: str,
        payload: Mapping[str, Any],
        wall: Optional[Mapping[str, Any]] = None,
    ) -> None:
        """Append one record.  ``payload`` must be deterministic run-to-run;
        anything wall-clock-dependent goes in ``wall`` (plus the record
        timestamp when enabled) — the quarantine that keeps identical runs
        byte-identical modulo the ``"wall"`` subtree."""
        if self._sink is None:
            return
        rec: Dict[str, Any] = {"kind": kind, **payload}
        w = dict(wall) if wall else {}
        if self.timestamps:
            w["ts"] = time.time()
        if w:
            rec["wall"] = w
        self._sink.write(json.dumps(rec, sort_keys=True) + "\n")
        self._sink.flush()

    def log_hist(self, name: str, hist: Optional[FixedHistogram] = None) -> None:
        """Write a named histogram record.  Latency counts are wall-clock
        dependent, so the whole payload sits under ``wall``."""
        h = hist if hist is not None else self._hists.get(name)
        if h is None:
            return
        self.log("hist", {"name": name}, wall={"hist": h.to_dict()})

    def log_spans(self, tracer) -> None:
        """Write the tracer's stage aggregate: span names and counts are
        deterministic (the schedule is), durations are wall-clock."""
        summary = tracer.stage_summary()
        self.log(
            "spans",
            {"counts": {k: v["count"] for k, v in summary.items()}},
            wall={"stages": summary},
        )

    # -- snapshot / delta ----------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        return {
            "counters": {k: c.value for k, c in sorted(self._counters.items())},
            "gauges": {k: g.value for k, g in sorted(self._gauges.items())},
            "hists": {k: h.to_dict() for k, h in sorted(self._hists.items())},
        }

    def delta(self, prev: Mapping[str, Any]) -> Dict[str, int]:
        """Counter movement since a previous :meth:`snapshot`."""
        base = prev.get("counters", {})
        return {
            k: c.value - int(base.get(k, 0))
            for k, c in sorted(self._counters.items())
        }

    def close(self) -> None:
        if self._sink is not None:
            self.log("summary", {"counters": self.snapshot()["counters"]})
            self._sink.close()
            self._sink = None

    def __enter__(self) -> "MetricsHub":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
