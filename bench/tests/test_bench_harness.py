"""CPU checks of the benchmark's parts: traffic, FLOP counts, lookup by
name, the peaks table, the reference's weight draw and ``BENCHMARK.json``'s
own rules."""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, peaks, spec
from bench.tests import tiny

BENCH = spec.load_benchmark()
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


def _config(name):
    return json.loads((spec.ROOT / CONFIGS[name]["file"]).read_text())


@pytest.mark.parametrize("mix_name", ["zipf", "drifting"])
def test_traffic_is_deterministic_and_equals_synth(mix_name):
    from repro.data import synth

    mix = (tiny.DRIFTING if mix_name == "drifting"
           else generator.load_mix(spec.BENCH / "traffic" / f"{mix_name}.json"))
    vocabs, seed = (1460, 583, 10_131_227, 3), 2**33 + 17
    base = synth.ZipfSparseSpec(vocab_sizes=vocabs, zipf_a=mix.zipf_a, n_dense=13)
    for step in (0, 9, 10, 31):
        a = generator.sparse_batch(mix, vocabs, 13, 256, seed, step)
        b = generator.sparse_batch(mix, vocabs, 13, 256, seed, step)
        if mix.drift_every:
            drift = synth.DriftingZipfSpec(base, mix.drift_every, mix.shift_fraction)
            want = synth.sparse_batch(base, 256, seed, step,
                                      id_shift=drift.shifts(step + mix.phase_offset))
        else:
            want = synth.sparse_batch(base, 256, seed, step)
        for k in want:
            np.testing.assert_array_equal(a[k], b[k])
            np.testing.assert_array_equal(a[k], want[k])
    other = generator.sparse_batch(mix, vocabs, 13, 256, seed + 1, 0)
    assert not np.array_equal(other["sparse"], a["sparse"])


def test_drift_mix_rotates_the_hot_set():
    mix = tiny.DRIFTING
    vocabs = (1000, 5000)
    first = mix.drift_every - mix.phase_offset  # the first step of phase 1
    assert mix.shifts(vocabs, first - 1).tolist() == [0, 0]
    assert mix.shifts(vocabs, first).tolist() == [370, 1850]
    assert mix.shifts(vocabs, first + mix.drift_every).tolist() == [740, 3700]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_flops_per_sample_equals_model_flops(name):
    from repro.configs import REGISTRY
    from repro.launch.model_flops import model_flops

    cfg = _config(name)
    fam = spec.family(cfg)
    arch = cfg["arch"]
    shape = REGISTRY[arch].shapes[0]
    assert fam.flops_per_sample(cfg) * cfg["batch_size"] == pytest.approx(
        model_flops(arch, shape), rel=1e-12)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_file_equals_the_program_config(name):
    from repro.launch.train import recsys_runner

    cfg = _config(name)
    fam = spec.family(cfg)
    model, _, _ = recsys_runner(cfg["arch"], batch=cfg["batch_size"],
                                model_shards=cfg["model_shards"])
    fam._check_sizes(model.cfg, cfg)  # raises on any difference
    assert cfg["reduced"] == CONFIGS[name]["reduced"]
    vocabs = cfg["published"]["vocab_sizes"] if "vocab_sizes" in cfg["reduced"] \
        else cfg["vocab_sizes"]
    assert sum(vocabs) == {"dlrm-criteo": 33_762_577}[cfg["arch"]]
    for k in cfg["reduced"]:
        assert cfg[k] != cfg["published"][k], k
    with pytest.raises(SystemExit, match="differs"):
        fam._check_sizes(model.cfg, dict(cfg, batch_size=cfg["batch_size"] + 1))

def test_parts_are_found_by_name():
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert spec.family(cell.config).build
        assert spec.reference(cell.config).run
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]).read)
    with pytest.raises(KeyError):
        spec.cell("no-such.cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peak("TPU v9", "bf16_flops_per_s")
    assert all(v.get("source") for v in peaks.PEAKS.values())


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + [w["name"] for w in BENCH["workloads"]]
             + [w["config"] for w in BENCH["workloads"]] + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(spec.NAME_RE.match(n) for n in names), names
    assert all(spec.UNIT_RE.match(m["unit"]) for m in BENCH["end_to_end"] + BENCH["per_layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in BENCH[group]]
        assert len(got) == len(set(got)), group
    assert len({m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}) == \
        len(BENCH["end_to_end"]) + len(BENCH["per_layer"])


def test_every_moves_target_is_reported_in_each_of_its_cells():
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    r = BENCH["run_seconds"]
    assert 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_reference_draws_the_rows_of_the_whole_table():
    ref = spec.reference(_config(sorted(CONFIGS)[0]))
    key = jnp.asarray(generator.seed_key(2**31 + 99))
    vocab, dim, bound = 70_001, 96, 1.0 / math.sqrt(96)
    whole = jax.jit(lambda k: jax.random.uniform(k, (vocab, dim), jnp.float32, -bound, bound))(key)
    rows = jnp.asarray([0, 1, 683, 70_000, 44_444], jnp.int32)
    got = jax.jit(ref.draw_rows, static_argnums=(2, 3))(key, rows, dim, bound)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole)[np.asarray(rows)])


@pytest.mark.parametrize("before,after,final,want", [
    # the reads raise the mark, the window does not (one chip, Criteo share)
    ([4_764_713_472, 4_772_125_696], [4_772_125_696, 4_772_125_696], 4_772_125_696,
     4_764_713_472),
    # a window step raises it past the reads
    ([100, 130], [120, 130], 150, 150),
    # steps between the reads raise it, the window does not
    ([100, 140], [120, 145], 145, 140),
])
def test_program_peak_leaves_out_the_reads(before, after, final, want):
    from bench import run as bench_run

    assert bench_run.program_peak(before, after, final) == want
