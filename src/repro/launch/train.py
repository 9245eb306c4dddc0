"""Training launcher: ``--arch <id>`` selects an architecture and runs the
fault-tolerant trainer on synthetic data.

``dlrm-avazu`` and ``dlrm-criteo`` build the registry configs at their
published widths (dim 128, the paper's MLPs, batch, 1.5% cache); ``dlrm``
and the other recsys archs build small CPU-scale models.  A slow tier that
does not fit the device's memory lives in host memory (``dlrm-criteo`` on
one v5e); the run's last lines say where it lived.  With
``--model-shards N`` on a host with at least N devices the cached slabs
split over the ``model`` axis of a hybrid (data, model) mesh.

  PYTHONPATH=src python -m repro.launch.train --arch dlrm --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-avazu --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-criteo --model-shards 4
  PYTHONPATH=src python -m repro.launch.train --arch din --steps 20
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.dist.partitioning as dist
from repro.data import graphs, synth
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_hybrid_mesh
from repro.train.trainer import PipelinedTrainer, Trainer, TrainerConfig

# archs whose DLRM comes from a registry config at published widths
PUBLISHED_DLRM = ("dlrm-avazu", "dlrm-criteo")


def cache_policy(name):
    """CLI string -> ``core.Policy`` (None passes the model default through)."""
    from repro.core.policies import Policy

    return Policy(name) if name else None


def dlrm_config(arch: str, batch=None, **cache_kw):
    """``DLRMConfig`` for a dlrm arch: the registry ``CONFIG`` of
    ``dlrm-avazu`` / ``dlrm-criteo`` (its batch unless ``batch`` is given),
    or the small ``dlrm`` model; ``cache_kw`` sets the cache and sharding
    fields on top."""
    from repro.models.dlrm import DLRMConfig

    if arch in PUBLISHED_DLRM:
        cfg = importlib.import_module(f"repro.configs.{arch.replace('-', '_')}").CONFIG
        if batch:
            cfg = dataclasses.replace(cfg, batch_size=batch)
        return dataclasses.replace(cfg, **cache_kw)
    return DLRMConfig(vocab_sizes=(100_000, 50_000, 20_000), embed_dim=32,
                      batch_size=batch or 512, cache_ratio=0.02, lr=0.3,
                      bottom_mlp=(64, 32), top_mlp=(64,), **cache_kw)


def recsys_runner(arch: str, batch=None, host_precision: str = "fp32",
                   model_shards: int = 0, policy=None,
                   replicate_top_k: int = 0, exchange_codec: str = "fp32",
                   max_routed_per_shard: int = 0,
                   arena_precision: str = "fp32",
                   use_pallas_plan: bool = False, chunk_rows: int = 0):
    if model_shards and not arch.startswith("dlrm"):
        raise SystemExit(f"--model-shards is wired for dlrm archs; {arch} "
                         f"builds an unsharded collection")
    if (replicate_top_k or exchange_codec != "fp32"
            or max_routed_per_shard) and not model_shards:
        raise SystemExit("--replicate-top-k / --exchange-codec / "
                         "--max-routed-per-shard shape the sharded exchange; "
                         "they need --model-shards >= 1")
    if arch in ("dlrm",) + PUBLISHED_DLRM:
        from repro.models.dlrm import DLRM

        cfg = dlrm_config(arch, batch, host_precision=host_precision,
                          arena_precision=arena_precision,
                          use_pallas_plan=use_pallas_plan, chunk_rows=chunk_rows,
                          model_shards=model_shards, policy=policy,
                          replicate_top_k=replicate_top_k,
                          exchange_codec=exchange_codec,
                          max_routed_per_shard=max_routed_per_shard)
        model = DLRM(cfg)
        spec = synth.ZipfSparseSpec(vocab_sizes=cfg.vocab_sizes, n_dense=cfg.n_dense)
        make = lambda s: {k: jnp.asarray(v) for k, v in synth.sparse_batch(
            spec, cfg.batch_size, 0, s).items()}
        return model, make, model.flush
    batch = batch or 512
    if arch == "fm":
        from repro.models.recsys_models import FMConfig, FMModel

        cfg = FMConfig(vocab_sizes=(100_000,) * 6, embed_dim=10, batch_size=batch,
                       cache_ratio=0.02, host_precision=host_precision,
                       arena_precision=arena_precision, policy=policy,
                       use_pallas_plan=use_pallas_plan, chunk_rows=chunk_rows)
        model = FMModel(cfg)
        spec = synth.ZipfSparseSpec(vocab_sizes=cfg.vocab_sizes)
        make = lambda s: {k: jnp.asarray(v) for k, v in synth.sparse_batch(spec, batch, 0, s).items()}
    elif arch in ("din", "dien", "mind"):
        from repro.models.recsys_models import (DIENConfig, DIENModel, DINConfig,
                                                DINModel, MINDConfig, MINDModel)

        if arch == "mind":
            cfg = MINDConfig(n_items=200_000, n_users=20_000, embed_dim=32,
                             seq_len=50, batch_size=batch, cache_ratio=0.05,
                             host_precision=host_precision,
                             arena_precision=arena_precision, policy=policy,
                             use_pallas_plan=use_pallas_plan,
                             chunk_rows=chunk_rows)
            model = MINDModel(cfg)
            make = lambda s: {k: jnp.asarray(v) for k, v in synth.recsys_batch(
                cfg.n_items, cfg.n_users, cfg.seq_len, batch, 0, s).items()}
        else:
            kw = dict(n_items=200_000, n_cates=20_000, n_users=20_000, embed_dim=18,
                      seq_len=50, batch_size=batch, cache_ratio=0.05,
                      host_precision=host_precision,
                      arena_precision=arena_precision, policy=policy,
                      use_pallas_plan=use_pallas_plan, chunk_rows=chunk_rows)
            cfg = DINConfig(**kw) if arch == "din" else DIENConfig(gru_dim=36, **kw)
            model = (DINModel if arch == "din" else DIENModel)(cfg)
            make = lambda s: {k: jnp.asarray(v) for k, v in synth.recsys_batch(
                cfg.n_items, cfg.n_users, cfg.seq_len, batch, 0, s, n_cates=cfg.n_cates).items()}
    else:
        raise ValueError(arch)

    return model, make, model.flush


def hybrid_shardings(model, mesh):
    """``(state, batch)`` NamedSharding trees of the hybrid-parallel layout on
    ``mesh``: every stacked collection leaf splits its shard axis over
    ``model`` (``collection.shard_specs``), dense params and optimizer state
    replicate, and the batch splits over ``data``."""
    with dist.axis_rules(mesh, dist.hybrid_rules()):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = dict(jax.tree_util.tree_map(lambda _: P(), shapes),
                 emb=model.collection.shard_specs())
    batch = model.input_specs(model.cfg.batch_size)
    bspecs = {k: P("data", *(None,) * (v.ndim - 1)) for k, v in batch.items()}
    named = lambda t: jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p), t, is_leaf=lambda x: isinstance(x, P))
    return named(specs), named(bspecs)


@dataclasses.dataclass
class Placement:
    """Where the state and batches of a run live (built by ``place``)."""

    init_fn: Callable[[], Any]
    make_batch: Callable[[int], Any]
    scope: Any  # logical-axis rule context every trace of the run runs under
    state_sh: Any = None  # state NamedSharding tree; None on one device

    def jit(self, fn, *, returns_metrics: bool = True, **kw):
        """``jax.jit(fn, **kw)``.  On a mesh the state ``fn`` returns (alone,
        or first of ``(state, metrics)``) keeps the state's layout, so the
        next step, and its donation, sees the same shardings."""
        if self.state_sh is not None:
            kw["out_shardings"] = (self.state_sh, None) if returns_metrics else self.state_sh
        return jax.jit(fn, **kw)

    @property
    def shard_fn(self) -> Optional[Callable[[Any], Any]]:
        """Lays a restored state out like a fresh one (None on one device)."""
        if self.state_sh is None:
            return None
        return lambda st: jax.device_put(st, self.state_sh)


def place(model, make, model_shards: int = 0, mesh=None) -> Placement:
    """Collection-backed models initialise under ``jax.jit``.  With
    ``model_shards`` and a mesh (``make_hybrid_mesh(model_shards)`` when the
    host has that many devices), init writes each shard straight to its
    device, so no device ever holds a whole table, and the batch is put on
    the ``data`` axis."""
    key = jax.random.PRNGKey(0)
    if not hasattr(model, "collection"):
        return Placement(lambda: model.init(key), make, contextlib.nullcontext())
    if model_shards and mesh is None and len(jax.devices()) >= model_shards:
        mesh = make_hybrid_mesh(model_shards)
    if mesh is None:
        return Placement(lambda: jax.jit(model.init)(key), make, contextlib.nullcontext())
    state_sh, batch_sh = hybrid_shardings(model, mesh)
    init = jax.jit(model.init, out_shardings=state_sh)
    return Placement(lambda: init(key), lambda s: jax.device_put(make(s), batch_sh),
                     dist.axis_rules(mesh, dist.hybrid_rules()), state_sh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=None,
                    help="batch size (default: the config's own for "
                         "dlrm-avazu / dlrm-criteo, 512 otherwise)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="0 = serial; k >= 1 = pipelined groups of k steps per "
                         "merged cache plan (collection-backed archs only)")
    ap.add_argument("--host-precision", default="fp32",
                    choices=["fp32", "fp16", "int8", "auto"],
                    help="host-tier embedding storage codec: fp32 = bit-exact "
                         "pre-store behavior; fp16/int8 shrink host bytes and "
                         "host<->device traffic; auto = PrecisionPolicy from "
                         "frequency stats (recsys archs only)")
    ap.add_argument("--arena-precision", default="fp32",
                    choices=["fp32", "fp16", "int8", "auto"],
                    help="device-arena (fast-tier) codec: fp32 = raw bit-exact "
                         "arena (pre-tiering behavior); fp16/int8 tier the "
                         "arena — the hot head stays fp32, the cold resident "
                         "tail stores encoded, stretching the same HBM over "
                         "2-4x more resident rows; auto = PrecisionPolicy "
                         "from head coverage (recsys archs only)")
    ap.add_argument("--model-shards", type=int, default=0,
                    help="0 = single-device collection; N >= 1 = hybrid "
                         "parallel: cached embedding slabs shard over N "
                         "model-axis shards, each with its own cache arena "
                         "and HostStore slice (dlrm archs; run under a mesh "
                         "whose model axis has N devices, or on one device "
                         "for functional testing)")
    ap.add_argument("--replicate-top-k", type=int, default=0,
                    help="hybrid parallel: K hottest ranks per cached slab "
                         "live in a replicated arena on every shard — their "
                         "lanes skip the all-to-all entirely (0 = off, "
                         "bit-identical layout to pre-replication)")
    ap.add_argument("--exchange-codec", default="fp32",
                    choices=["fp32", "fp16", "int8"],
                    help="hybrid parallel: codec for the routed row-leg of "
                         "the shard exchange; fp32 = bit-exact, fp16/int8 "
                         "shrink the cross-shard wire 2x/~4x")
    ap.add_argument("--max-routed-per-shard", type=int, default=0,
                    help="hybrid parallel: static per-shard plan-width bound "
                         "(0 = full-width planning).  Bounds the per-shard "
                         "cache-plan cost so planning stops scaling with the "
                         "shard count; too tight a bound raises through the "
                         "uniq_overflows guard instead of dropping lanes")
    ap.add_argument("--use-pallas-plan", action="store_true",
                    help="route cache planning through the bounded-top-K / "
                         "fused-prepare kernels (kernels/cache_ops): no "
                         "capacity-sized sort in the plan hot path.  "
                         "Bit-identical to the default route; Pallas lowers "
                         "on TPU/GPU, XLA references elsewhere (recsys archs "
                         "only)")
    ap.add_argument("--chunk-rows", type=int, default=0,
                    help="0 = scattered-row host staging (default); N = "
                         "stage host<->device embedding traffic in "
                         "contiguous N-row chunks (the paper's chunk-based "
                         "cache manager).  Bit-identical either way; values "
                         "that do not divide the table fall back to rows "
                         "(recsys archs only)")
    ap.add_argument("--cache-policy", default=None,
                    choices=["freq_lfu", "lru", "runtime_lfu", "uvm_row"],
                    help="cache eviction policy (core.policies.Policy): "
                         "freq_lfu = the paper's static frequency rank "
                         "(default), lru / uvm_row = recency, runtime_lfu = "
                         "online counters (recsys archs only)")
    ap.add_argument("--refresh-interval", type=int, default=0,
                    help="0 = static frequency ranking (the paper); N = "
                         "adaptive frequency engine: re-rank cached slabs "
                         "from online decayed counters every N steps "
                         "(pipelined runs refresh at group boundaries).  "
                         "Pure reindexing: fp32 losses are bit-identical "
                         "with or without it (recsys archs only)")
    ap.add_argument("--obs-dir", default=None,
                    help="observability output directory: streams per-step "
                         "JSONL (exact counters, loss, stage spans, the "
                         "step-time histogram) to <dir>/train.jsonl and a "
                         "Chrome trace to <dir>/train.trace.json; render "
                         "with `python -m repro.obs.report <dir>/train.jsonl`")
    ap.add_argument("--obs-annotate", action="store_true",
                    help="also enter jax.profiler.TraceAnnotation per stage "
                         "span so device-timeline captures carry the same "
                         "stage names")
    ap.add_argument("--history-limit", type=int, default=0,
                    help="0 = keep full in-memory history (legacy); N = keep "
                         "only the last N step records in memory (the full "
                         "stream is on disk when --obs-dir is set)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "gatedgcn":
        from repro.models.gatedgcn import GatedGCNConfig, GatedGCNModel

        model = GatedGCNModel(GatedGCNConfig(d_feat=32, n_classes=8, n_layers=8, d_hidden=32))
        indptr, indices, _ = graphs.random_graph_csr(20_000, 100_000, 0)
        feats = np.random.default_rng(0).normal(size=(20_000, 32)).astype(np.float32)
        labels = (feats[:, 0] > 0).astype(np.int32)
        make = lambda s: {k: jnp.asarray(v) for k, v in graphs.sampled_batch(
            indptr, indices, feats, labels, 256, (10, 5), 0, s).items()}
        flush = None
    elif args.arch in ("grok-1-314b", "olmoe-1b-7b", "gemma3-27b", "smollm-360m", "internlm2-20b"):
        import importlib

        from repro.models.lm import LMModel

        mod = importlib.import_module(f"repro.configs.{args.arch.replace('-', '_')}")
        model = LMModel(mod.SMOKE, lr=1e-3)  # reduced config for CPU training
        make = lambda s: {k: jnp.asarray(v) for k, v in synth.seq_batch(
            mod.SMOKE.vocab, 8, 64, 0, s).items()}
        flush = None
    else:
        model, make, flush = recsys_runner(args.arch, args.batch,
                                            args.host_precision, args.model_shards,
                                            policy=cache_policy(args.cache_policy),
                                            replicate_top_k=args.replicate_top_k,
                                            exchange_codec=args.exchange_codec,
                                            max_routed_per_shard=args.max_routed_per_shard,
                                            arena_precision=args.arena_precision,
                                            use_pallas_plan=args.use_pallas_plan,
                                            chunk_rows=args.chunk_rows)

    if args.cache_policy and not hasattr(model, "collection"):
        raise SystemExit(f"--cache-policy needs a collection-backed arch; "
                         f"{args.arch} has no embedding cache")
    refresh_fn = None
    if args.refresh_interval:
        if not hasattr(model, "refresh"):
            raise SystemExit(f"--refresh-interval needs a collection-backed "
                             f"arch; {args.arch} has no cached slabs to re-rank")
        refresh_fn = model.refresh
    tc = TrainerConfig(max_steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=25,
                       pipeline_depth=args.pipeline_depth,
                       refresh_interval=args.refresh_interval or None,
                       obs_dir=args.obs_dir, obs_annotate=args.obs_annotate,
                       history_limit=args.history_limit or None)
    where = place(model, make, args.model_shards)
    kw = dict(
        init_fn=where.init_fn,
        make_batch=where.make_batch,
        flush_fn=flush,
        refresh_fn=refresh_fn,
        on_straggler=lambda s, dt: print(f"[straggler] step {s}: {dt*1e3:.0f} ms"),
        shard_fn=where.shard_fn,
    )
    if args.pipeline_depth > 0:
        if not hasattr(model, "plan_step"):
            raise SystemExit(f"--pipeline-depth needs a collection-backed arch; "
                             f"{args.arch} has no split plan/compute step")
        # compute/apply consume the state they are passed, so donating arg 0
        # lets XLA update the cache arena in place instead of double-buffering
        # it.  plan_fn must NOT donate: planning reads the same state the
        # overlapped compute is still using.
        trainer = PipelinedTrainer(
            tc,
            plan_fn=jax.jit(model.plan_step),
            compute_fn=where.jit(model.compute_step, donate_argnums=(0,)),
            apply_fn=where.jit(model.apply_step, returns_metrics=False,
                               donate_argnums=(0,)),
            **kw,
        )
    else:
        trainer = Trainer(
            tc, step_fn=where.jit(model.train_step, donate_argnums=(0,)), **kw
        )
    with where.scope:
        state = trainer.run()
    h = trainer.history
    # history may be trimmed to a tail (--history-limit): report real steps
    print(f"\narch={args.arch} steps={h[-1]['step'] + 1} "
          f"loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f}")
    if "hit_rate" in h[-1]:
        print(f"cache hit rate: {h[-1]['hit_rate']:.1%}")
    if args.refresh_interval and "refresh_swaps" in h[-1]:
        print(f"adaptive refresh: {h[-1]['refresh_swaps']:.0f} rank swaps, "
              f"{h[-1]['refresh_rows_moved']:.0f} slow-tier rows moved, "
              f"window hit rate {h[-1].get('window_hit_rate', 0.0):.1%}")
    if hasattr(model, "collection"):
        db = model.collection.device_bytes()
        kinds = sorted({getattr(leaf.sharding, "memory_kind", None)
                        for slab in state["emb"].slabs.values() if hasattr(slab, "full")
                        for leaf in jax.tree_util.tree_leaves(slab.full)})
        print(f"host tier ({args.host_precision}): {db['slow_tier_bytes']/1e6:.1f} MB "
              f"(saved {db['host_bytes_saved']/1e6:.1f} MB vs fp32) in "
              f"{model.collection.slow_tier_memory} memory (its arrays: {kinds})")
        if args.arena_precision != "fp32":
            print(f"arena tier ({args.arena_precision}): saved "
                  f"{db.get('arena_bytes_saved', 0)/1e6:.2f} MB HBM vs fp32")
        if "host_wire_bytes" in h[-1]:
            print(f"host<->device traffic: {h[-1]['host_wire_bytes']/1e6:.1f} MB total")
        if args.model_shards:
            imb = h[-1].get("shard_imbalance", 1.0)
            print(f"hybrid parallel: {args.model_shards} shards, "
                  f"exchange {h[-1].get('exchange_bytes', 0)/1e6:.1f} MB total "
                  f"(ids {h[-1].get('exchange_id_bytes', 0)/1e6:.1f} MB + rows "
                  f"{h[-1].get('exchange_row_bytes', 0)/1e6:.1f} MB "
                  f"[{args.exchange_codec}], top-{args.replicate_top_k} "
                  f"replicated), live imbalance {imb:.2f}x")
    if args.obs_dir:
        print(f"observability: {trainer.hub.jsonl_path} "
              f"(render: python -m repro.obs.report {trainer.hub.jsonl_path}) "
              f"| chrome trace: {trainer.trace_path}")


if __name__ == "__main__":
    main()
