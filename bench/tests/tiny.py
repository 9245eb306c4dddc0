"""A CPU-sized DLRM cell for the harness tests: the launcher's toy ``dlrm``
arch, the real configuration's correctness limits."""
from __future__ import annotations

import json

from bench import generator, spec


# a mix whose hot set rotates; the generator supports it, no cell runs one yet
DRIFTING = generator.Mix(zipf_a=1.2, drift_every=10, shift_fraction=0.37, phase_offset=9)


def cell(model_shards: int = 0, mix: str = "zipf", share: int = 1) -> spec.Cell:
    """``share`` > 1 cuts each field's vocabulary to ``ceil(v / share)``, listed
    in ``reduced`` as a configuration file lists such a cut."""
    limits = json.loads((spec.BENCH / "configs" / "dlrm-criteo-1of4.json").read_text())["check"]
    cfg = {"name": "tiny", "family": "dlrm", "reference": "dlrm", "arch": "dlrm",
           "vocab_sizes": [100_000, 50_000, 20_000], "n_dense": 13, "embed_dim": 32,
           "bottom_mlp": [64, 32], "top_mlp": [64], "batch_size": 64, "lr": 0.3,
           "cache_ratio": 0.02, "max_unique_per_step": 0, "buffer_rows": 65536,
           "model_shards": model_shards, "setup_steps": 5, "check": limits, "reduced": []}
    if share > 1:
        published = cfg["vocab_sizes"]
        cfg.update(vocab_sizes=[-(-v // share) for v in published], reduced=["vocab_sizes"],
                   published={"vocab_sizes": published})
    bench = spec.load_benchmark()
    return spec.Cell("tiny", max(1, model_shards), cfg,
                     DRIFTING if mix == "drifting" else
                     generator.load_mix(spec.BENCH / "traffic" / f"{mix}.json"),
                     [m for m in bench["end_to_end"] if "workloads" not in m],
                     [m for m in bench["per_layer"] if "workloads" not in m])
