"""Training loop with the fault-tolerance features required at pod scale:

  * checkpoint/restart — async atomic checkpoints every N steps; on start the
    loop auto-resumes from the newest complete checkpoint (crash-safe), and
    because restore returns logical arrays, a restart may use a different
    mesh (elastic rescale) — shardings are re-applied here.
  * cached-embedding consistency — models with software-cache tiers get
    ``flush_fn`` called before every checkpoint so the slow tiers are
    authoritative (the caches stay warm); collection-era models pass
    ``model.flush`` (an ``EmbeddingCollection.flush`` over every cached
    slab), single-arena models wrap ``cached_embedding.flush_state``.
  * straggler detection — per-step wall times feed an EWMA + deviation
    monitor; steps slower than ``straggler_factor`` x the smoothed time fire
    ``on_straggler`` (log/report/abort — pluggable; on a real pod this wires
    into the coordinator's slow-host eviction).
  * overlap — host batch generation runs in a Prefetcher thread, and JAX
    async dispatch keeps device compute ahead of the Python loop; with
    ``TrainerConfig.pipeline_depth > 0`` the ``PipelinedTrainer`` runs the
    cache-prepare stage of step t+1 overlapped with step t's dense compute:
    planning (dedup + slot assignment + movement plan) reads only ids and
    cache index state, so it is dispatched before the trainer blocks on step
    t's loss, and the Prefetcher's lookahead window lets it prefetch rows
    needed k steps ahead (BagPipe, arXiv 2202.12429).  The serial ``Trainer``
    remains the bit-exactness oracle: both paths produce identical losses.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.data.pipeline import Prefetcher
from repro.obs import NULL_TRACER, MetricsHub, Tracer
from repro.train import checkpoint as ckpt_lib

__all__ = ["TrainerConfig", "Trainer", "PipelinedTrainer", "StragglerDetector"]


@dataclasses.dataclass
class StragglerDetector:
    """EWMA step-time monitor; flags abnormal steps (slow host / bad chip)."""

    factor: float = 3.0
    alpha: float = 0.1
    warmup: int = 5
    ewma: float = 0.0
    count: int = 0
    flagged: int = 0

    def observe(self, dt: float) -> bool:
        self.count += 1
        if self.count <= self.warmup:
            self.ewma = dt if self.ewma == 0 else (1 - self.alpha) * self.ewma + self.alpha * dt
            return False
        slow = dt > self.factor * max(self.ewma, 1e-9)
        if slow:
            self.flagged += 1
        else:  # stragglers don't poison the mean
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return slow


@dataclasses.dataclass
class TrainerConfig:
    max_steps: int
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    ckpt_keep: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    prefetch_depth: int = 2
    assert_no_uniq_overflow: bool = True
    # 0 = serial (one fused step_fn per step).  k >= 1 enables the pipelined
    # path (``PipelinedTrainer``): step t+1's cache plan is dispatched while
    # step t's dense compute runs, with the ids of the next k batches merged
    # into each plan so rows needed at t+k are prefetched before they miss.
    pipeline_depth: int = 0
    # None = static frequency ranking (the paper).  N = run the adaptive
    # re-ranking refresh (``refresh_fn``, usually ``model.refresh``) every N
    # steps — the serial trainer refreshes exactly on the cadence; the
    # pipelined trainer refreshes at the first GROUP BOUNDARY at or past each
    # multiple of N (a merged plan's addresses must never straddle a refresh).
    # Refresh is pure reindexing, so fp32 losses are bit-identical either way.
    refresh_interval: Optional[int] = None
    # -- observability -------------------------------------------------------
    # None keeps the hub sink-less: exact counters still accumulate, nothing
    # is written and span tracing is the zero-cost NULL_TRACER.  With a
    # directory, per-step records, the span aggregate, and the step-time
    # histogram stream to <obs_dir>/<obs_run>.jsonl and a Chrome trace is
    # exported at exit (render with ``python -m repro.obs.report``).
    obs_dir: Optional[str] = None
    obs_run: str = "train"
    # forward spans into jax.profiler.TraceAnnotation so the same stage names
    # label the device timeline under a ``jax.profiler.trace`` capture
    obs_annotate: bool = False
    # None = unbounded in-memory history (legacy behavior).  N = keep only
    # the last N records in memory; with obs_dir set the full stream is on
    # disk anyway, so long runs stop accumulating O(steps) host memory.
    history_limit: Optional[int] = None


class Trainer:
    def __init__(
        self,
        cfg: TrainerConfig,
        init_fn: Callable[[], Any],  # () -> state
        step_fn: Callable[[Any, Dict], Any],  # (state, batch) -> (state, metrics); jitted
        make_batch: Callable[[int], Dict],  # step -> host batch
        flush_fn: Optional[Callable[[Any], Any]] = None,  # cache barrier pre-ckpt
        on_straggler: Optional[Callable[[int, float], None]] = None,
        shard_fn: Optional[Callable[[Any], Any]] = None,  # re-shard after restore
        refresh_fn: Optional[Callable[[Any], Any]] = None,  # adaptive re-rank
        # ^ host-side pure-reindexing pass (``model.refresh``), run every
        #   ``cfg.refresh_interval`` steps (pipelined: at group boundaries)
    ):
        self.cfg = cfg
        self.init_fn = init_fn
        self.step_fn = step_fn
        self.make_batch = make_batch
        self.flush_fn = flush_fn
        self.on_straggler = on_straggler
        self.shard_fn = shard_fn
        self.refresh_fn = refresh_fn
        self.detector = StragglerDetector(factor=cfg.straggler_factor)
        self.checkpointer = (
            ckpt_lib.Checkpointer(cfg.ckpt_dir, keep=cfg.ckpt_keep) if cfg.ckpt_dir else None
        )
        self.history: List[Dict[str, float]] = []
        # ONE wrap-safe reconstruction point for every cumulative in-jit
        # int32 counter (hits/misses, host rows/bytes, exchange lanes/bytes,
        # refresh swaps): the hub accumulates exact Python-int totals even
        # with no sink; with cfg.obs_dir it also streams the run's JSONL.
        self.hub = MetricsHub(run_dir=cfg.obs_dir, run=cfg.obs_run)
        self.tracer = (
            Tracer(annotate=cfg.obs_annotate)
            if (cfg.obs_dir or cfg.obs_annotate)
            else NULL_TRACER
        )
        self.trace_path: Optional[str] = None

    # -- state bootstrap -----------------------------------------------------
    def _bootstrap(self):
        state = self.init_fn()
        start = 0
        if self.checkpointer is not None:
            try:
                restored, start = self.checkpointer.restore_latest(state)
                state = restored
                if self.shard_fn is not None:
                    state = self.shard_fn(state)  # elastic: new mesh, same logical state
            except FileNotFoundError:
                pass
        return state, start

    # -- shared per-step bookkeeping (both execution paths) ------------------
    def _post_step(self, step_i: int, state: Any, metrics: Dict, t0: float) -> Any:
        """Block on the loss scalar, record history, run the straggler /
        overflow monitors and the checkpoint cadence; returns the (possibly
        flushed) state."""
        cfg = self.cfg
        # block on one scalar so step time is real, rest stays async.  Up to
        # three more fetches follow (overflow guard, float telemetry, hub
        # counters); each has its own ``host-transfer*`` span, so a profile
        # shows which one the loop blocks on and the device idles under.
        with self.tracer.span("host-transfer"):
            loss = float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        if self.detector.observe(dt) and self.on_straggler:
            self.on_straggler(step_i, dt)
        if cfg.assert_no_uniq_overflow and "uniq_overflows" in metrics:
            with self.tracer.span("host-transfer.overflow"):
                n_over = int(jax.device_get(metrics["uniq_overflows"]))
            if n_over:
                raise RuntimeError(
                    f"cache unique-buffer overflow at step {step_i}: raise "
                    f"max_unique_per_step (per-table TableConfig bound, or the "
                    f"arena bound for GROUPED tables — exactness violated otherwise)"
                )
        rec: Dict[str, Any] = {"step": step_i, "loss": loss, "time_s": dt}
        float_keys = [
            k
            for k in ("auc", "hit_rate", "cache_evictions", "grad_norm",
                      "xent", "shard_imbalance", "window_hit_rate",
                      "refresh_swaps", "refresh_rows_moved")
            if k in metrics
        ]
        if float_keys:  # one fetch for all float telemetry, not one per key
            with self.tracer.span("host-transfer.telemetry"):
                fetched = jax.device_get({k: metrics[k] for k in float_keys})
            rec.update({k: float(v) for k, v in fetched.items()})
        # every cumulative int32 counter family in the metrics dict —
        # hits/misses, host rows and ENCODED wire bytes, exchange lanes and
        # id/row-leg bytes, refresh swaps — reconstructs to exact wrap-free
        # Python ints through the hub (the one family table lives in
        # repro.obs.hub; hit_rate_exact rides along when both hit families
        # are present).  A float32 accumulator loses integer resolution past
        # 2^24 and the in-jit int32 counters wrap past 2^31; neither survives
        # a long run, which is why everything routes through the hub.
        with self.tracer.span("host-transfer.counters"):
            rec.update(self.hub.observe_embedding_metrics(metrics))
            if "host_wire_bytes" not in rec and "host_wire_bytes" in metrics:
                # legacy metrics dicts carry only the float32 scalar fallback
                rec["host_wire_bytes"] = float(
                    jax.device_get(metrics["host_wire_bytes"])
                )
        self.hub.histogram("step_time_s").observe(dt)
        self.hub.log(
            "step",
            {k: v for k, v in rec.items() if k != "time_s"},
            wall={"time_s": dt},
        )
        self.history.append(rec)
        if cfg.history_limit is not None and len(self.history) > cfg.history_limit:
            # tests index and slice history, so it stays a plain list; trim
            # the head in place to bound host memory on long runs
            del self.history[: len(self.history) - cfg.history_limit]
        last = step_i + 1 >= cfg.max_steps
        if self.checkpointer and ((step_i + 1) % cfg.ckpt_every == 0 or last):
            with self.tracer.span("checkpoint"):
                to_save = state
                if self.flush_fn is not None:
                    to_save = self.flush_fn(state)
                    state = to_save  # flushed state stays valid to train on
                self.checkpointer.save_async(step_i + 1, to_save)
        return state

    def _finish_obs(self) -> None:
        """Flush the run's observability artifacts — the step-time histogram,
        the span aggregate, the counter summary, and the Chrome trace.
        Idempotent and called from the run loop's ``finally`` so a crashed
        run still leaves a renderable JSONL."""
        self.hub.log_hist("step_time_s")
        self.hub.log_spans(self.tracer)
        if self.cfg.obs_dir:
            self.trace_path = self.tracer.export_chrome_trace(
                os.path.join(self.cfg.obs_dir, f"{self.cfg.obs_run}.trace.json")
            )
        self.hub.close()

    def run(self) -> Any:
        cfg = self.cfg
        state, start = self._bootstrap()
        if start >= cfg.max_steps:
            self._finish_obs()
            return state
        prefetch = Prefetcher(self.make_batch, start_step=start, depth=cfg.prefetch_depth)
        try:
            for step_i, batch in prefetch:
                if step_i >= cfg.max_steps:
                    break
                t0 = time.perf_counter()
                with self.tracer.span("step"):
                    state, metrics = self.step_fn(state, batch)
                state = self._post_step(step_i, state, metrics, t0)
                if (
                    self.refresh_fn is not None
                    and cfg.refresh_interval
                    and (step_i + 1) % cfg.refresh_interval == 0
                    and step_i + 1 < cfg.max_steps
                ):
                    with self.tracer.span("refresh"):
                        state = self.refresh_fn(state)
            if self.checkpointer:
                self.checkpointer.wait()
        finally:
            prefetch.close()
            self._finish_obs()
        return state


class PipelinedTrainer(Trainer):
    """Two-stage pipelined execution with lookahead cache prefetch.

    The fused step is split into the model's three stages:

      ``plan_fn(state, batch, future_batches) -> plan``   weight-free: dedup,
          slot assignment, movement plan; merges the lookahead window's ids so
          rows needed k steps ahead load early and are pinned until used.
      ``compute_fn(state, batch, addresses) -> (state, metrics)``   dense
          fwd/bwd + optimizer + synchronous row update.
      ``apply_fn(state, plan) -> state``   executes the planned row movement.

    Steps run in GROUPS of ``pipeline_depth``: one merged plan admits the
    whole group's rows (addresses for every member come from the same plan),
    so the per-step bookkeeping — dedup, victim argsort, transmitter rounds —
    is paid once per group instead of once per step.  The next group's plan is
    dispatched at the FIRST compute of the current group, before the trainer
    blocks on any loss: planning reads only ids and cache index arrays (which
    the compute step passes through untouched), so a multi-stream runtime is
    free to overlap it with the dense work, and the prepare stage leaves the
    loss-to-loss critical path either way.  Its row movement is applied after
    the group's last row update, so evictions write back fresh values.

    ``pipeline_depth=1`` is the pure BagPipe pipeline (plan t+1 under compute
    t); larger depths add the amortization.  Because planning never reads
    weights and compute never reads the index arrays, any depth is
    loss-bit-identical to the serial ``Trainer`` (tested property) when the
    host tier stores fp32.  With a lossy host codec (fp16/int8 ``HostStore``)
    the schedules agree only to codec noise: lookahead pinning keeps a
    soon-needed row resident where the serial schedule would evict
    (quantize) and reload (dequantize) it, so the pipelined path sees
    strictly FEWER quantization round trips — same parity tolerance, not
    bitwise equality.

    The exact ids of future batches come from ``Prefetcher.lookahead`` — the
    BagPipe observation that training data is read ahead anyway, so there is
    nothing speculative about prefetching embedding rows.  Running a group off
    one plan needs the union of its unique rows to fit the cache: the trainer
    checks the plan's ``future_unresident`` counter and fails fast with the
    remedy (raise the cache ratio or lower ``pipeline_depth``) instead of
    silently gathering zeros.

    Telemetry caveat: cache hit/miss counters are recorded by the plan, so
    under group scheduling they sample only the group leaders' batches (1/k
    of the traffic); losses and transfer correctness are unaffected.
    """

    def __init__(
        self,
        cfg: TrainerConfig,
        init_fn: Callable[[], Any],
        plan_fn: Callable[[Any, Dict, tuple], Any],  # jitted (state, batch, window)
        compute_fn: Callable[[Any, Dict, Any], Any],  # jitted (state, batch, addresses)
        apply_fn: Callable[[Any, Any], Any],  # jitted (state, plan)
        make_batch: Callable[[int], Dict],
        flush_fn: Optional[Callable[[Any], Any]] = None,
        on_straggler: Optional[Callable[[int, float], None]] = None,
        shard_fn: Optional[Callable[[Any], Any]] = None,
        refresh_fn: Optional[Callable[[Any], Any]] = None,
    ):
        super().__init__(
            cfg,
            init_fn,
            step_fn=None,
            make_batch=make_batch,
            flush_fn=flush_fn,
            on_straggler=on_straggler,
            shard_fn=shard_fn,
            refresh_fn=refresh_fn,
        )
        self.plan_fn = plan_fn
        self.compute_fn = compute_fn
        self.apply_fn = apply_fn

    @staticmethod
    def _take(prefetch, n: int) -> list:
        """Up to ``n`` (step, batch) pairs; a short list means the stream
        ended (mirrors ``Prefetcher.lookahead``'s contract)."""
        out = []
        for _ in range(n):
            try:
                out.append(next(prefetch))
            except StopIteration:
                break
        return out

    def _check_window(self, plan, group) -> None:
        """A group runs off one merged plan only if every member's rows made
        residency — fail fast with the remedy otherwise."""
        if len(group) <= 1:
            return
        n = int(jax.device_get(plan.future_unresident))
        if n:
            raise RuntimeError(
                f"pipelined group of {len(group)} steps needs all its unique rows "
                f"resident at once, but {n} lookahead lanes were dropped under "
                f"capacity pressure: raise the cache ratio or lower "
                f"TrainerConfig.pipeline_depth"
            )

    def run(self) -> Any:
        cfg = self.cfg
        depth = max(1, cfg.pipeline_depth)
        state, start = self._bootstrap()
        if start >= cfg.max_steps:
            self._finish_obs()
            return state
        prefetch = Prefetcher(
            self.make_batch, start_step=start, depth=max(cfg.prefetch_depth, depth)
        )
        try:
            group = self._take(prefetch, min(depth, cfg.max_steps - start))
            if not group:  # stream ended before the first step
                return state
            # prologue: the first group has no shadow to plan under
            with self.tracer.span("plan"):
                plan = self.plan_fn(
                    state, group[0][1], tuple(b for _, b in group[1:])
                )
            self._check_window(plan, group)
            with self.tracer.span("apply"):
                state = self.apply_fn(state, plan)
            addrs = (plan.addresses,) + tuple(plan.future_addresses)
            refresh_on = self.refresh_fn is not None and cfg.refresh_interval
            # align the cadence to ABSOLUTE step numbers so a checkpoint
            # restore resumes the same refresh schedule (the serial trainer's
            # modulo check is restore-aligned by construction)
            next_refresh_at = (
                (start // cfg.refresh_interval + 1) * cfg.refresh_interval
                if refresh_on
                else None
            )
            while group:
                next_plan = None
                last_step = group[-1][0]
                n_next = min(depth, cfg.max_steps - (last_step + 1))
                # refresh only at GROUP BOUNDARIES: a merged plan's addresses
                # are computed against one index image, so a group must never
                # straddle the re-rank.  When a refresh falls due inside this
                # group, the next group's plan is NOT dispatched early — it is
                # planned after the refresh, from the refreshed index state
                # (one serial prepare per refresh_interval steps).
                refresh_now = (
                    refresh_on
                    and last_step + 1 >= next_refresh_at
                    and n_next > 0
                )
                for j, (step_i, batch) in enumerate(group):
                    t0 = time.perf_counter()
                    if j == 0 and n_next > 0 and not refresh_now:
                        # dispatch the NEXT group's merged plan before blocking
                        # on any of this group's losses — planning reads only
                        # ids + index state, so it overlaps the dense compute.
                        # A short peek means the STREAM ENDED (the lookahead
                        # contract): the final group shrinks to what is left
                        # rather than planning batches that will never come.
                        peek = prefetch.lookahead(n_next)
                        n_next = len(peek)
                        if peek:
                            with self.tracer.span("plan"):
                                next_plan = self.plan_fn(
                                    state, peek[0][1],
                                    tuple(b for _, b in peek[1:]),
                                )
                    with self.tracer.span("compute"):
                        state, metrics = self.compute_fn(state, batch, addrs[j])
                    if j == len(group) - 1 and next_plan is not None:
                        # movement runs after the group's last row update:
                        # evictions write back the freshest values
                        with self.tracer.span("apply"):
                            state = self.apply_fn(state, next_plan)
                    state = self._post_step(step_i, state, metrics, t0)
                if refresh_now:
                    with self.tracer.span("refresh"):
                        state = self.refresh_fn(state)
                    done = last_step + 1
                    next_refresh_at = (
                        done // cfg.refresh_interval + 1
                    ) * cfg.refresh_interval
                    peek = prefetch.lookahead(n_next)
                    n_next = len(peek)
                    if peek:
                        with self.tracer.span("plan"):
                            next_plan = self.plan_fn(
                                state, peek[0][1], tuple(b for _, b in peek[1:])
                            )
                        with self.tracer.span("apply"):
                            state = self.apply_fn(state, next_plan)
                if next_plan is None:
                    break
                group = self._take(prefetch, n_next)
                self._check_window(next_plan, group)
                addrs = (next_plan.addresses,) + tuple(next_plan.future_addresses)
            if self.checkpointer:
                self.checkpointer.wait()
        finally:
            prefetch.close()
            self._finish_obs()
        return state
