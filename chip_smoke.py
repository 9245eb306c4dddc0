"""Run the DLRM cache trainer's main path on a TPU and check what comes out.

    python chip_smoke.py                # one chip: phases 1-3
    python chip_smoke.py --four-chips   # four chips: the sharded phase only

One chip:
  1. ``dlrm-avazu`` at its published config (9.4M rows x dim 128, batch
     65536, 1.5% cache) on the default planning route: warm-up, then timed
     steps through the launcher's ``Trainer`` and the model's
     ``train_step``.
  2. The same config and seed with ``use_pallas_plan=True``, so the
     compiled ``victim_threshold`` kernel plans every step; its losses must
     equal phase 1's bit for bit.
  3. Each ``cache_ops`` Pallas kernel, compiled, against its ``ref.py``
     function on the same inputs at Avazu widths: exact equality.

Four chips (``--four-chips``):
  * ``dlrm-criteo`` at its published config (33.8M rows x dim 128, batch
    16384) with ``model_shards=4`` on a (data=1, model=4) mesh: finite
    losses, each device's peak bytes, and the stacked shard axis on four
    distinct devices.
  * ``dlrm-avazu`` with ``model_shards=4`` on the mesh against
    ``model_shards=0`` on one chip: per-step losses agree to 1e-6 relative.

Everything runs in this one process.  Without a TPU the script exits
non-zero and prints no result.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

WARMUP = 1  # steps run before the timed window
STEPS = 5  # timed steps per training phase
LOSS_RTOL = 1e-6  # sharded vs single-chip Avazu losses
SEED = 0  # of the phase-3 kernel inputs


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def release(tree) -> None:
    """Free a finished phase's device arrays before the next one."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def train(tag: str, arch: str, model_shards: int = 0, mesh=None, **cache_kw):
    """``WARMUP + STEPS`` launcher steps of ``arch``; returns the phase's
    record and its final state."""
    import jax

    from repro.launch.train import place, recsys_runner
    from repro.train.trainer import Trainer, TrainerConfig

    model, make, flush = recsys_runner(arch, model_shards=model_shards, **cache_kw)
    where = place(model, make, model_shards, mesh)
    with where.scope:
        t0 = time.perf_counter()
        state = jax.block_until_ready(where.init_fn())
        init_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        step = where.jit(model.train_step, donate_argnums=(0,))
        compiled = step.lower(state, where.make_batch(0)).compile()
        compile_s = time.perf_counter() - t0
        step_s = []

        def timed(st, batch):
            t = time.perf_counter()
            out = jax.block_until_ready(compiled(st, batch))
            step_s.append(time.perf_counter() - t)
            return out

        held = [state]  # the trainer takes the only reference: step 0 donates it
        del state
        trainer = Trainer(
            TrainerConfig(max_steps=WARMUP + STEPS, log_every=WARMUP + STEPS),
            init_fn=held.pop,
            step_fn=timed,
            make_batch=where.make_batch,
            flush_fn=flush,
        )
        state = trainer.run()
    hist = trainer.history
    rec = {
        "phase": tag,
        "arch": arch,
        "model_shards": model_shards,
        "batch": model.cfg.batch_size,
        "init_s": init_s,
        "compile_s": compile_s,
        "step_s": step_s[WARMUP:],
        "loss": [h["loss"] for h in hist],
        "hit_rate": [h["hit_rate"] for h in hist],
        "pallas_kernel_in_step": "tpu_custom_call" in compiled.as_text(),
        "peak_bytes_in_use": [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()],
    }
    print(json.dumps(rec), flush=True)
    return rec, model, state


def kernels(capacity: int, dim: int, lanes: int, num_shards: int = 4):
    """Phase 3: every cache_ops kernel, compiled, equals its reference."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.cache_ops import kernel, ops, ref
    from repro.store.codec import get_codec

    check(ops.kernels_enabled(), "cache_ops kernels are not enabled on this backend")
    rng = np.random.default_rng(SEED)
    results = {}

    # bounded top-K victims: a tie-heavy eviction key over the whole arena
    key = jnp.asarray(rng.integers(-50_000, 50_000, size=capacity), jnp.int32)
    kv = min(lanes, capacity)
    want = jax.jit(ref.victim_topk, static_argnums=1)(key, kv)
    got = jax.jit(ops.victim_topk_impl, static_argnums=1)(key, kv)
    results["victim_threshold"] = bool(jnp.array_equal(want, got))

    # [S, lanes] routing image
    owner = jnp.asarray(rng.integers(-1, num_shards, size=lanes), jnp.int32)
    local = jnp.asarray(rng.integers(-1, capacity, size=lanes), jnp.int32)
    want = jax.jit(ref.bucketize, static_argnums=2)(owner, local, num_shards)
    got = jax.jit(kernel.bucketize_pallas, static_argnums=2)(owner, local, num_shards)
    results["bucketize"] = bool(jnp.array_equal(want, got))

    # tiered-arena gather + decode: fp32 head, fp16 / int8 tail
    h = capacity // 4  # arena_head_ratio 0.25
    head = jnp.asarray(rng.normal(size=(h, dim)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(capacity - h, dim)), jnp.float32)
    slots = jnp.asarray(rng.integers(-2, capacity + 2, size=lanes), jnp.int32)
    for codec in ("fp16", "int8"):
        c = get_codec(codec)
        tail, side = jax.jit(c.encode)(rows)
        want = jax.jit(functools.partial(ref.arena_gather, decode=c.decode,
                                         out_dtype=jnp.float32))(head, tail, side, slots)
        got = jax.jit(functools.partial(kernel.gather_decode_pallas, codec=codec))(
            head, tail, side, slots)
        results[f"gather_decode_{codec}"] = bool(jnp.array_equal(want, got))
    print(json.dumps({"phase": "kernels", "capacity": capacity, "dim": dim,
                      "lanes": lanes, "equal": results}), flush=True)
    check(all(results.values()), f"kernel differs from its reference: {results}")


def one_chip() -> None:
    from repro.core.collection import SHARED_ARENA

    base, model, state = train("default_route", "dlrm-avazu")
    capacity = model.collection.cached_slabs[SHARED_ARENA].capacity
    release(state)
    check(all(map(_finite, base["loss"])), f"non-finite loss {base['loss']}")
    fused, _, state = train("pallas_plan", "dlrm-avazu", use_pallas_plan=True)
    release(state)
    check(fused["pallas_kernel_in_step"], "no Pallas kernel in the use_pallas_plan step")
    check(fused["loss"] == base["loss"],
          f"use_pallas_plan losses {fused['loss']} != default {base['loss']}")
    # the plan width: every field's lanes of one batch
    kernels(capacity, model.cfg.embed_dim,
            lanes=model.cfg.batch_size * len(model.cfg.vocab_sizes))


def four_chips() -> None:
    import jax

    from repro.core.collection import SHARED_ARENA
    from repro.launch.mesh import make_hybrid_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = make_hybrid_mesh(4, n_devices=len(devices))
    rec, _, state = train("criteo_sharded", "dlrm-criteo", model_shards=4, mesh=mesh)
    check(all(map(_finite, rec["loss"])), f"non-finite loss {rec['loss']}")
    limit = min(d.memory_stats()["bytes_limit"] for d in devices)
    check(max(rec["peak_bytes_in_use"]) < limit,
          f"peak bytes {rec['peak_bytes_in_use']} over the device limit {limit}")
    table = state["emb"].slabs[SHARED_ARENA].full.data["weight"]
    placed = sorted((s.index[0].start or 0, s.device.id) for s in table.addressable_shards)
    print(json.dumps({"phase": "criteo_placement", "shape": list(table.shape),
                      "shard_start_row_and_device": placed}), flush=True)
    check(len({d for _, d in placed}) == 4 and len({r for r, _ in placed}) == 4,
          f"stacked shard axis not on four distinct devices: {placed}")
    release(state)

    sharded, _, state = train("avazu_sharded", "dlrm-avazu", model_shards=4, mesh=mesh)
    release(state)
    single, _, state = train("avazu_single", "dlrm-avazu")
    release(state)
    rel = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(sharded["loss"], single["loss"])]
    print(json.dumps({"phase": "avazu_sharded_vs_single", "max_rel_diff": max(rel),
                      "max_abs_diff": max(abs(a - b) for a, b in
                                          zip(sharded["loss"], single["loss"]))}), flush=True)
    check(max(rel) <= LOSS_RTOL, f"sharded vs single-chip losses differ by {max(rel)}")


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (needs 4 devices)")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
