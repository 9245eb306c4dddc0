"""Serving launcher: batched scoring with the cache in read-only mode.

  PYTHONPATH=src python -m repro.launch.serve --arch mind --requests 2000
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.data import synth
from repro.serve.engine import ServeEngine


def main():
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import cache_policy

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mind", choices=["mind", "din", "dlrm-criteo"])
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--arena-precision", default="fp32",
                    choices=["fp32", "fp16", "int8", "auto"],
                    help="device-arena (fast-tier) codec: fp32 = raw bit-exact "
                         "arena; fp16/int8 tier it (fp32 hot head + encoded "
                         "resident tail) so the same HBM holds 2-4x more "
                         "resident rows; auto = PrecisionPolicy pick")
    ap.add_argument("--cache-policy", default=None,
                    choices=["freq_lfu", "lru", "runtime_lfu", "uvm_row"],
                    help="cache eviction policy (core.policies.Policy); "
                         "default = the model's (freq_lfu)")
    ap.add_argument("--refresh-interval", type=int, default=0,
                    help="0 = static ranking; N = adaptive frequency engine: "
                         "re-rank the read-only cache from online decayed "
                         "counters every N scored batches (pure reindexing — "
                         "scores unchanged, hit rate adapts to traffic)")
    ap.add_argument("--obs-dir", default=None,
                    help="observability output directory: streams per-batch "
                         "JSONL (exact counters, the latency histogram, span "
                         "aggregates) to <dir>/serve.jsonl and a Chrome "
                         "trace to <dir>/serve.trace.json; render with "
                         "`python -m repro.obs.report <dir>/serve.jsonl`")
    args = ap.parse_args()
    enable_compile_cache()
    policy = cache_policy(args.cache_policy)

    if args.arch == "mind":
        from repro.models.recsys_models import MINDConfig, MINDModel

        cfg = MINDConfig(n_items=200_000, n_users=20_000, embed_dim=32, seq_len=50,
                         batch_size=args.batch, cache_ratio=0.05,
                         arena_precision=args.arena_precision, policy=policy)
        model = MINDModel(cfg)
        pad = {"hist_items": np.zeros((cfg.seq_len,), np.int32),
               "hist_len": np.zeros((), np.int32), "user": np.zeros((), np.int32),
               "target_item": np.zeros((), np.int32), "label": np.zeros((), np.float32)}
        mk = lambda s: synth.recsys_batch(cfg.n_items, cfg.n_users, cfg.seq_len,
                                          args.batch, 1, s)
    elif args.arch == "din":
        from repro.models.recsys_models import DINConfig, DINModel

        cfg = DINConfig(n_items=200_000, n_cates=20_000, n_users=20_000, embed_dim=18,
                        seq_len=50, batch_size=args.batch, cache_ratio=0.05,
                        arena_precision=args.arena_precision, policy=policy)
        model = DINModel(cfg)
        pad = {k: np.zeros(s, np.int32) for k, s in (
            ("hist_items", (cfg.seq_len,)), ("hist_cates", (cfg.seq_len,)),
            ("hist_len", ()), ("target_item", ()), ("target_cate", ()), ("user", ()))}
        pad["label"] = np.zeros((), np.float32)
        mk = lambda s: synth.recsys_batch(cfg.n_items, cfg.n_users, cfg.seq_len,
                                          args.batch, 1, s, n_cates=cfg.n_cates)
    else:
        from repro.models.dlrm import DLRM, DLRMConfig

        cfg = DLRMConfig(vocab_sizes=(100_000, 50_000), embed_dim=32, batch_size=args.batch,
                         cache_ratio=0.05, bottom_mlp=(64, 32), top_mlp=(64,),
                         arena_precision=args.arena_precision, policy=policy)
        model = DLRM(cfg)
        pad = {"dense": np.zeros((13,), np.float32), "sparse": np.zeros((2,), np.int32),
               "label": np.zeros((), np.float32)}
        spec = synth.ZipfSparseSpec(vocab_sizes=cfg.vocab_sizes, n_dense=13)
        mk = lambda s: synth.sparse_batch(spec, args.batch, 1, s)

    state = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(
        model.serve_step, state, batch_size=args.batch, pad_example=pad,
        state_stats_fn=lambda s: model.collection.metrics(s["emb"], writeback=False),
        # read-only cache: resident rows are clean, the re-rank skips writebacks
        refresh_fn=(lambda s: model.refresh(s, writeback=False))
        if args.refresh_interval else None,
        refresh_every=args.refresh_interval or None,
        obs_dir=args.obs_dir,
    )
    n = 0
    step = 0
    while n < args.requests:
        b = mk(step)
        engine.score(b)
        n += args.batch
        step += 1
    summary = engine.summary()
    engine.close()
    print("stats:", summary)
    print(f"cache hit rate: {summary['hit_rate']:.1%} | "
          f"host<->device traffic: {summary['host_wire_bytes']/1e6:.2f} MB")
    if args.obs_dir:
        print(f"observability: {engine.hub.jsonl_path} "
              f"(render: python -m repro.obs.report {engine.hub.jsonl_path}) "
              f"| chrome trace: {engine.trace_path}")


if __name__ == "__main__":
    main()
