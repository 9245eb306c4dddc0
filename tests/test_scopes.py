"""The compiled step's named scopes, the row-level hit counter and the
trainer's fetch spans: what a profiler trace of the step is split by."""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synth
from repro.models.dlrm import DLRM, DLRMConfig
from repro.obs import NULL_TRACER, STEP_SCOPES, MetricsHub
from repro.train.trainer import Trainer, TrainerConfig

REPO = Path(__file__).resolve().parents[1]
BASE = dict(vocab_sizes=(2048, 256, 64), embed_dim=8, batch_size=16,
            cache_ratio=0.15, lr=0.2, bottom_mlp=(16, 8), top_mlp=(16,))
UNSHARDED_SCOPES = tuple(s for s in STEP_SCOPES if s != "shard.exchange")


def _make(cfg):
    spec = synth.ZipfSparseSpec(vocab_sizes=cfg.vocab_sizes, n_dense=13)
    return lambda s: {k: jnp.asarray(v)
                      for k, v in synth.sparse_batch(spec, cfg.batch_size, 0, s).items()}


def scope_paths(hlo: str):
    """Every ``op_name`` of the HLO text, split into its path components with
    transform wrappers (``jvp(..)``, ``transpose(..)``) peeled."""
    out = []
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        parts = []
        for p in name.split("/"):
            while (m := re.match(r"^(?!p?jit\()[\w\-]+\((.*)\)$", p)):
                p = m.group(1)
            parts.append(p)
        out.append((name, parts))
    return out


def assert_scoped(hlo: str, scopes):
    paths = scope_paths(hlo)
    for s in scopes:
        assert any(s in parts for _, parts in paths), s
    # the backward of a scope keeps its name
    assert any(n.split("/")[1].startswith("transpose(jvp(cache.lookup))")
               for n, _ in paths if n.count("/") > 1)
    assert any(n.split("/")[1].startswith("transpose(jvp(dense))")
               for n, _ in paths if n.count("/") > 1)
    # the hit probe nests inside the plan
    assert any(parts[1:3] == ["cache.plan", "telemetry"] for _, parts in paths)


def test_compiled_step_carries_every_scope():
    cfg = DLRMConfig(**BASE)
    model = DLRM(cfg)
    state = model.init(jax.random.PRNGKey(0))
    hlo = jax.jit(model.train_step).lower(state, _make(cfg)(0)).compile().as_text()
    assert_scoped(hlo, UNSHARDED_SCOPES)
    assert "shard.exchange" not in hlo


def test_split_stages_carry_the_fused_steps_scopes():
    cfg = DLRMConfig(**BASE)
    model = DLRM(cfg)
    state = model.init(jax.random.PRNGKey(0))
    batch = _make(cfg)(0)
    plan = jax.jit(model.plan_step).lower(state, batch).as_text(debug_info=True)
    assert "cache.plan" in plan and "cache.movement" not in plan
    p = model.plan_step(state, batch)
    apply = jax.jit(model.apply_step).lower(state, p).as_text(debug_info=True)
    assert "cache.movement" in apply and "cache.plan" not in apply
    compute = jax.jit(model.compute_step).lower(state, batch, p.addresses).as_text(
        debug_info=True)
    for s in ("cache.lookup", "dense", "cache.row_update", "telemetry"):
        assert s in compute, s


def run_sub(code: str, n_dev: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), str(REPO / "tests")])
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_step_on_4_devices_carries_the_exchange_scope():
    """On a (data=1, model=4) mesh the sharded lookup's cross-shard leg
    runs under ``shard.exchange``, nested in ``cache.lookup``."""
    out = run_sub("""
        import jax
        from repro.launch.train import place
        from repro.models.dlrm import DLRM, DLRMConfig
        from repro.obs import STEP_SCOPES
        import test_scopes as ts

        cfg = DLRMConfig(**ts.BASE, model_shards=4)
        model = DLRM(cfg)
        where = place(model, ts._make(cfg), 4)
        with where.scope:
            state = where.init_fn()
            step = where.jit(model.train_step, donate_argnums=(0,))
            hlo = step.lower(state, where.make_batch(0)).compile().as_text()
        ts.assert_scoped(hlo, STEP_SCOPES)
        assert any(parts[1:3] == ["cache.lookup", "shard.exchange"]
                   for _, parts in ts.scope_paths(hlo))
        print("OK", len(jax.devices()))
    """)
    assert "OK 4" in out


# --------------------------------------------------------------------------
# the row-level hit counter against a NumPy reference
# --------------------------------------------------------------------------


def reference_rows(coll, emb, fb, sharded: bool):
    """(unique rows the batch asks for, of them resident before the plan),
    summed over the cached slabs, from the state's maps in NumPy."""
    n_uniq = n_res = 0
    for sname in coll.cached_slabs:
        slab = emb.slabs[sname]
        raw = []
        for f, _ in coll._slab_lanes(fb, sname):
            ids = np.asarray(fb.ids[f]).reshape(-1)
            off = coll.table_slab[coll.feature_to_table[f]][1]
            raw.append(ids[ids >= 0] + off)
        ranks = np.unique(np.asarray(slab.idx_map)[np.concatenate(raw)])
        r2s = np.asarray(slab.cache.row_to_slot)
        if sharded:
            res = r2s[np.asarray(slab.rank_owner)[ranks], np.asarray(slab.rank_local)[ranks]]
        else:
            res = r2s[ranks]
        n_uniq += len(ranks)
        n_res += int(np.sum(res >= 0))
    return n_uniq, n_res


def check_uniq_rows(model, state, step, make, steps: int, sharded: bool):
    """Each step's ``cache_uniq_rows`` / ``cache_misses`` deltas equal the
    reference's unique rows and non-resident unique rows; returns the row hit
    rates."""
    hub = MetricsHub()
    prev = None
    rates = []
    for s in range(steps):
        batch = make(s)
        n_uniq, n_res = reference_rows(model.collection, state["emb"],
                                       model.features(batch), sharded)
        state, m = step(state, batch)
        rec = hub.observe_embedding_metrics(m)
        du = rec["cache_uniq_rows"] - (prev["cache_uniq_rows"] if prev else 0)
        dm = rec["cache_misses"] - (prev["cache_misses"] if prev else 0)
        assert (du, dm) == (n_uniq, n_uniq - n_res), (s, du, dm, n_uniq, n_res)
        rates.append(1.0 - dm / du)
        prev = rec
    assert any(0 < r < 1 for r in rates), rates  # misses and hits both happen
    return rates


def test_uniq_rows_counts_unique_rows_of_each_plan():
    cfg = DLRMConfig(**BASE)
    model = DLRM(cfg)
    state = model.init(jax.random.PRNGKey(0))
    check_uniq_rows(model, state, jax.jit(model.train_step), _make(cfg), 4, False)


def test_uniq_rows_on_4_shards_counts_unique_rows_of_each_shard_plan():
    """Sharded, each shard plans its deduplicated share: the counter sums
    to the batch's unique rows, in the unit of ``misses`` (``hits`` counts
    lanes unsharded and unique rows sharded; this counter is one unit)."""
    out = run_sub("""
        import jax
        from repro.launch.train import place
        from repro.models.dlrm import DLRM, DLRMConfig
        import test_scopes as ts

        cfg = DLRMConfig(**ts.BASE, model_shards=4)
        model = DLRM(cfg)
        where = place(model, ts._make(cfg), 4)
        with where.scope:
            state = where.init_fn()
            step = where.jit(model.train_step)
            rates = ts.check_uniq_rows(model, state, step, where.make_batch, 4, True)
        print("OK", len(jax.devices()), rates)
    """)
    assert "OK 4" in out


# --------------------------------------------------------------------------
# the trainer's post-step fetch spans
# --------------------------------------------------------------------------

FETCH_SPANS = ("host-transfer", "host-transfer.overflow", "host-transfer.telemetry",
               "host-transfer.counters")


@pytest.mark.parametrize("annotate", [True, False])
def test_trainer_spans_each_post_step_fetch(annotate):
    cfg = DLRMConfig(**BASE)
    model = DLRM(cfg)
    tr = Trainer(TrainerConfig(max_steps=3, obs_annotate=annotate),
                 init_fn=lambda: model.init(jax.random.PRNGKey(0)),
                 step_fn=jax.jit(model.train_step), make_batch=_make(cfg))
    tr.run()
    summary = tr.tracer.stage_summary()
    if annotate:
        assert {k: summary[k]["count"] for k in FETCH_SPANS} == dict.fromkeys(FETCH_SPANS, 3)
        assert summary["step"]["count"] == 3
    else:
        assert tr.tracer is NULL_TRACER and summary == {}
    assert "cache_uniq_rows" in tr.history[-1]
