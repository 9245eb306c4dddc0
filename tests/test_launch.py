"""The training launcher's model builders, placement and compile cache."""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import dlrm_avazu, dlrm_criteo
from repro.launch import compile_cache
from repro.launch.train import dlrm_config, place, recsys_runner

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("arch,registry", [("dlrm-avazu", dlrm_avazu.CONFIG),
                                           ("dlrm-criteo", dlrm_criteo.CONFIG)])
def test_published_dlrm_builds_the_registry_config(arch, registry):
    model, _, _ = recsys_runner(arch)
    assert model.cfg == registry  # every published field, launcher defaults on top
    assert model.cfg.embed_dim == 128
    assert model.cfg.bottom_mlp == (512, 256, 128)
    assert model.cfg.top_mlp == (1024, 1024, 512, 256)
    assert model.cfg.cache_ratio == 0.015


def test_published_dlrm_takes_cache_and_shard_settings():
    cfg = dlrm_config("dlrm-criteo", batch=128, model_shards=4, use_pallas_plan=True)
    assert cfg == dataclasses.replace(dlrm_criteo.CONFIG, batch_size=128,
                                      model_shards=4, use_pallas_plan=True)


def test_toy_dlrm_stays_reachable():
    model, make, _ = recsys_runner("dlrm", 32)
    assert model.cfg.vocab_sizes == (100_000, 50_000, 20_000)
    assert make(0)["sparse"].shape == (32, 3)


def test_place_without_a_mesh_jits_init_on_one_device():
    model, make, _ = recsys_runner("dlrm", 16)
    where = place(model, make)
    assert where.state_sh is None and where.shard_fn is None
    assert where.make_batch is make
    state = where.init_fn()
    assert len(state["emb"].slabs["__shared__"].full.data["weight"].devices()) == 1


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_compile_cache_honours_the_environment(monkeypatch):
    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_place_shards_the_state_over_four_devices():
    """``--model-shards 4`` on a 4-device host: init writes one shard per
    device and the step keeps that layout, at the single-device losses."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.launch.train import place, recsys_runner

        def run(shards):
            model, make, _ = recsys_runner("dlrm", 16, model_shards=shards)
            where = place(model, make, shards)
            with where.scope:
                state = where.init_fn()
                step = where.jit(model.train_step, donate_argnums=(0,))
                losses = []
                for i in range(3):
                    state, m = step(state, where.make_batch(i))
                    losses.append(float(m["loss"]))
            return state, losses

        state, sharded = run(4)
        w = state["emb"].slabs["__shared__"].full.data["weight"]
        assert len({s.device for s in w.addressable_shards}) == 4, w.sharding
        assert sorted(s.index[0].start or 0 for s in w.addressable_shards) == [0, 1, 2, 3]
        _, single = run(0)
        np.testing.assert_allclose(sharded, single, rtol=1e-6)
        print("PLACED")
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PLACED" in out.stdout
