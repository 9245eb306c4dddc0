"""The DLRM family: builds the program's DLRM for a ``dlrm-*`` configuration
through the launcher's normal path, and reads its parameters back for the
comparison with ``configs/dlrm.py``.

The model comes from ``repro.launch.train.recsys_runner`` (the registry
config named by the file's ``arch``), its ``train_step`` is jitted with the
state donated and placed as ``repro.launch.train.place`` places it: on one
device, or with ``model_shards`` on ``make_hybrid_mesh``.  Only the initial
key differs from the launcher: it comes from the run's seed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator, spec

__all__ = ["System", "build", "flops_per_sample", "SIZE_KEYS"]

# keys of a configuration file that must equal the program's DLRMConfig
SIZE_KEYS = ("vocab_sizes", "n_dense", "embed_dim", "bottom_mlp", "top_mlp",
             "batch_size", "cache_ratio", "lr", "max_unique_per_step",
             "buffer_rows", "model_shards")


def flops_per_sample(cfg: Mapping) -> float:
    """Useful FLOPs of one training sample: 3 x forward (backward = 2 x
    forward) of the bottom MLP, the pairwise interaction and the top MLP;
    embedding lookups and the SGD update count nothing."""
    f1 = len(cfg["vocab_sizes"]) + 1
    bottom = tuple(cfg["bottom_mlp"])
    top = tuple(cfg["top_mlp"])
    bot = 2 * sum(a * b for a, b in zip((cfg["n_dense"],) + bottom[:-1], bottom))
    inter = 2 * f1 * f1 * cfg["embed_dim"]
    top_in = cfg["embed_dim"] + f1 * (f1 - 1) // 2
    top_f = 2 * sum(a * b for a, b in zip((top_in,) + top, top + (1,)))
    return 3.0 * (bot + inter + top_f)


READ_CHUNK = 1024  # rows per read call: 0.5 MB of rows at a time


@dataclasses.dataclass
class System:
    """One built program: its model, where its state lives, and the calls a
    run needs."""

    cfg: Mapping
    model: Any
    init: Callable[[], Any]  # () -> state, drawn on the device from the seed
    make_batch: Callable[[int], Dict[str, Any]]  # step -> placed batch
    host_batch: Callable[[int], Dict[str, np.ndarray]]  # step -> host batch
    step: Any  # jitted train_step, state donated
    flush: Any  # jitted model.flush, state donated
    lookup: Dict[str, Any]  # table -> jitted full_lookup(emb state, ids)
    scope: Any  # logical-axis rule context the run traces under

    @property
    def batch_size(self) -> int:
        return int(self.cfg["batch_size"])

    def read(self, state, touched: Sequence[np.ndarray]):
        """The parameters as ``configs/dlrm.py``'s named leaves: every MLP leaf
        and, per field, the rows of the ``touched`` ids (in their order).  The
        cache barrier ``model.flush`` first makes the slow tier current, then
        the rows are read through ``full_lookup`` in fixed chunks, so the read
        compiles once and holds little device memory.  Returns the state
        (donated through the flush) and the leaves."""
        state = self.flush(state)
        params = jax.device_get(state["params"])
        flat = []
        for tower in ("bottom", "top"):
            layers = params[tower]
            for i in range(len(layers)):
                flat += [np.asarray(layers[f"l{i}"]["w"]), np.asarray(layers[f"l{i}"]["b"])]
        for name, ids in zip(self.model.feature_names, touched):
            parts = []
            for c in range(0, len(ids), READ_CHUNK):
                chunk = np.full(READ_CHUNK, -1, np.int32)
                n = len(ids[c:c + READ_CHUNK])
                chunk[:n] = ids[c:c + READ_CHUNK]
                parts.append(np.asarray(self.lookup[name](state["emb"], chunk))[:n])
            flat.append(np.concatenate(parts) if parts else
                        np.zeros((0, self.cfg["embed_dim"]), np.float32))
        names = spec.reference(self.cfg).leaf_names(self.cfg)
        return state, dict(zip(names, flat))


def _lookup(collection, table, emb_state, ids):
    return collection.full_lookup(emb_state, table, ids)


def _norm(v):
    return tuple(v) if isinstance(v, (list, tuple)) else v


def _check_sizes(model_cfg, cfg: Mapping) -> None:
    """The program's registry config must equal the file, key by key; a key
    the file lists in ``reduced`` is compared with its ``published`` value."""
    want = {k: cfg["published"][k] if k in cfg["reduced"] else cfg[k] for k in SIZE_KEYS}
    bad = {k: (getattr(model_cfg, k), want[k]) for k in SIZE_KEYS
           if _norm(getattr(model_cfg, k)) != _norm(want[k])}
    if bad:
        raise SystemExit(f"the program's {cfg['arch']} differs from the "
                         f"configuration file (program, file): {bad}")


def build(cfg: Mapping, mix: generator.Mix, seed: int, devices: List[Any],
          model_override: Callable[[Any], Any] | None = None) -> System:
    """Build the program for ``cfg`` on ``devices``.  ``model_override`` maps
    the built model to another one (the calibration's control and faults)."""
    from repro.launch.mesh import make_hybrid_mesh
    from repro.launch.train import place, recsys_runner

    shards = int(cfg["model_shards"])
    model, _, _ = recsys_runner(cfg["arch"], batch=cfg["batch_size"], model_shards=shards)
    _check_sizes(model.cfg, cfg)
    if cfg["reduced"]:  # the chip's share of the published configuration
        model = type(model)(dataclasses.replace(
            model.cfg, **{k: _norm(cfg[k]) for k in cfg["reduced"]}))
    if model_override is not None:
        model = model_override(model)
    vocabs, n_dense, batch = cfg["vocab_sizes"], cfg["n_dense"], cfg["batch_size"]

    def host_batch(step: int) -> Dict[str, np.ndarray]:
        return generator.sparse_batch(mix, vocabs, n_dense, batch, seed, step)

    def to_device(step: int):
        return {k: jnp.asarray(v) for k, v in host_batch(step).items()}

    mesh = make_hybrid_mesh(shards, n_devices=len(devices)) if shards else None
    where = place(model, to_device, shards, mesh)
    key = jnp.asarray(generator.seed_key(seed))
    if where.state_sh is None:
        init = jax.jit(model.init)
    else:
        init = jax.jit(model.init, out_shardings=where.state_sh)
    return System(
        cfg=cfg,
        model=model,
        init=lambda: init(key),
        make_batch=where.make_batch,
        host_batch=host_batch,
        step=where.jit(model.train_step, donate_argnums=(0,)),
        flush=where.jit(model.flush, returns_metrics=False, donate_argnums=(0,)),
        lookup={n: jax.jit(functools.partial(_lookup, model.collection, n))
                for n in model.feature_names},
        scope=where.scope,
    )
