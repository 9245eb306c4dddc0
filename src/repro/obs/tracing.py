"""Host-side span tracing for the pipelined stages.

The pipelined trainer's whole value proposition is *overlap* — plan t+1
dispatched under compute t — and BagPipe's lesson (arXiv 2202.12429) is that
those wins are only real if you can see which stage hides which latency.
``Tracer`` records named wall-clock spans at the stage boundaries the Python
loop actually controls (plan / compute / apply / refresh / host-transfer /
checkpoint / score) and exports them as Chrome-trace JSON, so a run renders
directly in ``chrome://tracing`` / Perfetto with one row per thread and the
group structure visible.

Two caveats, by design:

* JAX dispatch is asynchronous — a span around ``compute_fn(...)`` measures
  *dispatch* time unless something blocks inside it.  The blocking points
  are explicit in the trainer (the per-step loss fetch is the
  ``host-transfer`` span; the overflow guard, float telemetry and hub
  counter fetches after it are ``host-transfer.overflow`` /
  ``.telemetry`` / ``.counters``), so the span profile shows where the
  Python loop spends wall-clock, which is exactly the quantity the pipeline
  overlaps.
* device-side timing needs the real profiler: with ``annotate=True`` every
  span also enters a ``jax.profiler.TraceAnnotation``, so the same stage
  names appear on the device timeline when a ``jax.profiler.trace`` capture
  is taken around the run.

Raw events are capped (``max_events``, default 100k) so a week-long serve
loop cannot grow without bound — aggregate stats (count / total per name)
stay exact past the cap.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List

__all__ = ["Tracer", "NULL_TRACER", "STEP_SCOPES"]

# The compiled step's layers, as ``jax.named_scope`` names.  Each stage of
# ``models.common.CollectionTrainStep`` runs under one, so every op of the
# jitted step carries its layer in the HLO ``op_name`` metadata and a
# profiler trace splits device time by layer.  Two nest inside others:
# ``telemetry`` also names the plan's id-level hit probe
# (``core.cache.plan_prepare``), ``shard.exchange`` the sharded lookup's
# cross-shard leg (``core.sharded``).  ``cache.host_link`` names the legs
# that cross the host<->device link when the slow tier lives in host memory
# (``core.transmitter``; nested in ``cache.movement`` in the step); it is
# not in ``STEP_SCOPES`` because a step whose slow tier is in HBM has none.
SCOPE_PLAN = "cache.plan"
SCOPE_MOVEMENT = "cache.movement"
SCOPE_LOOKUP = "cache.lookup"
SCOPE_EXCHANGE = "shard.exchange"
SCOPE_DENSE = "dense"
SCOPE_ROW_UPDATE = "cache.row_update"
SCOPE_TELEMETRY = "telemetry"
SCOPE_HOST_LINK = "cache.host_link"
STEP_SCOPES = (SCOPE_PLAN, SCOPE_MOVEMENT, SCOPE_LOOKUP, SCOPE_EXCHANGE,
               SCOPE_DENSE, SCOPE_ROW_UPDATE, SCOPE_TELEMETRY)


class Tracer:
    """Named wall-clock spans with Chrome-trace export.

    Thread-safe: the serve engine's replica workers and the trainer's
    prefetch thread may all record spans; events carry the recording
    thread's id so the Chrome trace renders one row per thread.
    """

    def __init__(self, annotate: bool = False, max_events: int = 100_000):
        self.annotate = annotate
        self.max_events = max_events
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._dropped = 0
        # exact aggregates, never capped: name -> [count, total_seconds]
        self._agg: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        """Record one ``name`` span around the body (optionally annotating
        the device timeline via ``jax.profiler.TraceAnnotation``)."""
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax.profiler  # deferred: tracing stays importable sans jax

            ann = jax.profiler.TraceAnnotation(name)
        start = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                dur = time.perf_counter() - start
                self._record(name, start - self._t0, dur, attrs)

    def _record(self, name: str, ts: float, dur: float, attrs: Dict) -> None:
        with self._lock:
            agg = self._agg.setdefault(name, [0, 0.0])
            agg[0] += 1
            agg[1] += dur
            if len(self._events) >= self.max_events:
                self._dropped += 1
                return
            ev = {"name": name, "ts": ts, "dur": dur,
                  "tid": threading.get_ident()}
            if attrs:
                ev["args"] = dict(attrs)
            self._events.append(ev)

    # -- aggregates ----------------------------------------------------------

    def stage_summary(self) -> Dict[str, Dict[str, float]]:
        """Exact per-stage aggregates: ``{name: {count, total_s, mean_ms}}``
        (counts survive the raw-event cap)."""
        with self._lock:
            return {
                name: {
                    "count": int(c),
                    "total_s": t,
                    "mean_ms": 1e3 * t / c if c else 0.0,
                }
                for name, (c, t) in sorted(self._agg.items())
            }

    @property
    def dropped_events(self) -> int:
        return self._dropped

    # -- export --------------------------------------------------------------

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace/Perfetto JSON object (``ph: "X"`` complete events,
        microsecond timestamps relative to tracer start)."""
        with self._lock:
            events = [
                {
                    "name": ev["name"],
                    "ph": "X",
                    "ts": round(ev["ts"] * 1e6, 3),
                    "dur": round(ev["dur"] * 1e6, 3),
                    "pid": os.getpid(),
                    "tid": ev["tid"],
                    **({"args": ev["args"]} if "args" in ev else {}),
                }
                for ev in self._events
            ]
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path`` (atomic rename so a
        crashed run never leaves a half-written trace); returns the path."""
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)
        return path


class _NullTracer(Tracer):
    """Zero-overhead stand-in when observability is off: ``span`` returns a
    shared nullcontext, records nothing."""

    def __init__(self):
        super().__init__(annotate=False, max_events=0)
        self._null = contextlib.nullcontext()

    def span(self, name: str, **attrs: Any):  # noqa: ARG002 - interface parity
        return self._null


NULL_TRACER: Tracer = _NullTracer()
