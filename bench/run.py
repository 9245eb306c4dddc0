"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run builds the program for the cell's configuration, draws its weights on
the device from ``--seed``, and drives the launcher's serial ``Trainer``
with the compiled, state-donating ``train_step``.  Set-up (``setup_s``, from
process start to the first window step) compiles or loads every program,
runs the first steps, and reads the parameters back after the first and the
third step.  The window then runs whole steps for ``--seconds`` seconds of
host clock, from the dispatch of its first step to the loss of its last one
being ready.  After the window the peak device memory is read (without the
parameter reads' own few MB: ``program_peak``), the program's
state is freed, and the plain reference follows the first three steps from
the same seed; ``check.py`` compares the two.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces the
window with the JAX profiler and reports its per-layer metrics, each read by
``metrics/<name>.py``.  The last line of stdout is one JSON object; the last
lines of stderr give each compared number beside its limit.  Without a TPU,
or with fewer chips than the cell asks for, the run prints no result and
exits 2.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, generator, spec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

COMPARED_STEPS = 3
READ_AFTER = (1, COMPARED_STEPS)  # read the parameters before these steps run
WINDOW_SPAN = "bench.window_step"


@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read (``metrics/<name>.py``)."""

    cell: spec.Cell
    device_kind: str
    chips: int
    batch_size: int
    flops_per_sample: float
    window_steps: int
    window_s: float
    samples_per_s: float
    step_times_s: List[float]  # host clock of each window step
    hits: Optional[int]  # cache hits over the window (exact counters)
    misses: Optional[int]
    devices: Dict[int, Any]  # traced: device id -> trace.DeviceWindow


def _peak(devices) -> int:
    """Peak bytes in use on the fullest device (CPU reports none: 0)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def program_peak(before: List[int], after: List[int], final: int) -> int:
    """The peak of the program's own work, without the parameter reads.

    ``before[k]`` / ``after[k]`` are the high-water marks just before and
    just after read k, ``final`` the one after the window.  The reads raise
    the mark by a few MB (their flush and lookups), so the mark before the
    first read stands, which covers init, the compile and one whole step of
    the program the window runs; a later mark counts only where steps
    between reads, or the window, raised it past everything before them."""
    peak = before[0]
    for prev, nxt in zip(after, before[1:] + [final]):
        if nxt > prev:
            peak = nxt
    return peak


def _free(tree) -> None:
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


class _Loop:
    """The step the trainer calls: the compiled ``train_step``, plus the
    set-up's parameter reads and the window's clock and stop."""

    def __init__(self, system, compiled, touched, devices, setup_steps: int, seconds: float,
                 on_trace_start: Optional[Callable[[], None]]):
        self.system, self.compiled, self.touched = system, compiled, touched
        self.devices = devices
        self.setup_steps, self.seconds = setup_steps, seconds
        self.on_trace_start = on_trace_start
        self.trainer = None
        self.i = 0
        self.enter: List[float] = []  # host clock as the trainer calls step i
        self.snaps: Dict[int, Any] = {}  # step -> leaves read before it ran
        self.peaks: List[int] = []  # device peak bytes before each read
        self.peaks_after: List[int] = []  # and after it
        self.t_start = 0.0

    def __call__(self, state, batch):
        import jax

        i = self.i
        self.i += 1
        now = time.perf_counter()
        self.enter.append(now)
        if i in READ_AFTER:
            self.peaks.append(_peak(self.devices))
            state, self.snaps[i] = self.system.read(state, self.touched)
            self.peaks_after.append(_peak(self.devices))
        if i < self.setup_steps:
            if i == self.setup_steps - 1 and self.on_trace_start is not None:
                self.on_trace_start()
            return self.compiled(state, batch)
        if i == self.setup_steps:
            self.t_start = now
        if now - self.t_start >= self.seconds:
            self.trainer.cfg.max_steps = i + 1  # the window's last step
        if self.on_trace_start is None:
            return self.compiled(state, batch)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            return self.compiled(state, batch)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, devices: List[Any],
        t0: float = T0, model_override=None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object (see the module doc)."""
    import jax

    from repro.train.trainer import Trainer, TrainerConfig

    cfg = cell.config
    fam = spec.family(cfg)
    ref = spec.reference(cfg)
    setup_steps = int(cfg["setup_steps"])
    if setup_steps <= COMPARED_STEPS:
        raise ValueError(f"setup_steps {setup_steps} must exceed {COMPARED_STEPS}")
    system = fam.build(cfg, cell.mix, seed, devices, model_override)
    first = [system.host_batch(s) for s in range(COMPARED_STEPS)]
    touched = ref.touched_ids(first, len(cfg["vocab_sizes"]))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    with system.scope:
        state = jax.block_until_ready(system.init())
        compiled = system.step.lower(state, system.make_batch(0)).compile()
        hlo = compiled.as_text() if trace else ""
        loop = _Loop(system, compiled, touched, devices, setup_steps, seconds,
                     start_trace if trace else None)
        held = [state]  # the trainer takes the only reference: step 0 donates it
        del state
        trainer = Trainer(
            TrainerConfig(max_steps=1 << 30, log_every=1 << 30, obs_annotate=trace),
            init_fn=held.pop, step_fn=loop, make_batch=system.make_batch)
        loop.trainer = trainer
        try:
            state = trainer.run()
        finally:
            if trace:
                jax.profiler.stop_trace()
    hist = trainer.history
    win = hist[setup_steps:]
    t_end = loop.enter[-1] + hist[-1]["time_s"]
    window_s = t_end - loop.t_start
    final_peak = _peak(devices)  # read before the reference runs
    peak = program_peak(loop.peaks, loop.peaks_after, final_peak)
    _free(state)
    del state, compiled, trainer

    # the plain reference follows the compared steps from the same seed
    ref_out = ref.run(cfg, generator.seed_key(seed), first, COMPARED_STEPS)
    read = check.readings(
        ref_out, [h["loss"] for h in hist[:COMPARED_STEPS]],
        loop.snaps[READ_AFTER[0]], loop.snaps[READ_AFTER[1]], float(cfg["lr"]))
    judged = check.judge(read, cfg["check"])
    correct = check.passed(judged)

    samples_per_s = len(win) * system.batch_size / window_s
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    out: Dict[str, Any] = {"correct": correct, "attempted": len(win), "failed": 0}
    if not trace:
        values = {"samples_per_s": samples_per_s, "hbm_peak_gb": peak / 1e9,
                  "setup_s": loop.t_start - t0}
        out["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        rec, breakdown, busy = _traced(cell, system, fam, hlo, trace_dir, win, hist,
                                       setup_steps, window_s, samples_per_s, devices)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["metrics"] = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(rec)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy)
        out["breakdown"] = breakdown
    out["device"] = dev
    out["detail"] = dict(read["detail"], peak_bytes_before_reads=loop.peaks,
                         peak_bytes_after_reads=loop.peaks_after, peak_bytes_final=final_peak)
    out["compared"] = judged  # last key: each compared number beside its limit
    return out


def _traced(cell, system, fam, hlo, trace_dir, win, hist, setup_steps, window_s,
            samples_per_s, devices):
    """Reduce the window's trace: the reader record, the breakdown and the
    device's busy and window seconds."""
    from bench import trace as tr

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    t = tr.load(files[0])
    marks = [s for s in t.host if s.name == WINDOW_SPAN]
    if not marks:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    thread = marks[0].module
    w0 = min(s.start for s in marks)
    ends = [s.end for s in t.host if s.name == "host-transfer" and s.module == thread]
    w1 = max(ends) if ends else max(s.end for s in marks)
    cats = tr.hlo_categories(hlo)
    module = hlo.split("\n", 1)[0].split()[1].rstrip(",") if hlo.startswith("HloModule") else ""
    used = sorted(t.devices)[: len(devices)]
    dw = {d: tr.reduce_device(t.devices[d], w0, w1, cats, module) for d in used}
    busy_s = statistics.fmean(w.busy_ns for w in dw.values()) / 1e9
    traced_window_s = (w1 - w0) / 1e9

    ops: Dict[str, float] = {}
    for w in dw.values():
        for name, ns in w.by_op.items():
            label = f"{name} [{tr.categorize(tr.Span(name, 0, 0), cats, '')}]"
            ops[label] = ops.get(label, 0.0) + ns / 1e9 / len(dw)
    gaps: Dict[str, float] = {}
    for w in dw.values():
        names = tr.host_activity(t.host, [(a + b) / 2 for a, b in w.gaps], thread)
        for (a, b), name in zip(w.gaps, names):
            gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9 / len(dw)
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    first, last = hist[setup_steps - 1], hist[-1]
    hits = last.get("cache_hits", 0) - first.get("cache_hits", 0) if "cache_hits" in last else None
    misses = (last.get("cache_misses", 0) - first.get("cache_misses", 0)
              if "cache_misses" in last else None)
    rec = RunRecord(
        cell=cell, device_kind=devices[0].device_kind, chips=len(devices),
        batch_size=system.batch_size, flops_per_sample=fam.flops_per_sample(cell.config),
        window_steps=len(win), window_s=window_s, samples_per_s=samples_per_s,
        step_times_s=[h["time_s"] for h in win], hits=hits, misses=misses, devices=dw)
    return (rec, {"device_ops": top(ops), "idle_gaps": top(gaps)},
            {"busy_s": busy_s, "window_s": traced_window_s})


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative integer")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    out = run(cell, args.seed, args.seconds, bool(args.trace), devices[: cell.chips])
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
