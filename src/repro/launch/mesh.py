"""Production meshes.

Single pod:  (data=16, model=16)          = 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)   = 512 chips

Functions, not module constants, so importing never touches jax device state.
The ``pod`` axis composes with ``data`` for batch/FSDP sharding, so the same
rule tables scale to N pods — DCN traffic is whatever lands on the ``pod``
axis (gradient all-reduce, optionally compressed via optim.compression).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "make_hybrid_mesh"]


def _make(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (benchmarks use 1..8-device slices)."""
    return _make(tuple(shape), tuple(axes))


def make_hybrid_mesh(model_shards: int, n_devices: int | None = None):
    """The hybrid-parallel (data, model) mesh for a sharded collection.

    ``model`` gets exactly ``model_shards`` devices (the shard count of a
    ``ShardedEmbeddingCollection`` must equal the model-axis size so the
    stacked state splits one shard per device); the remaining factor becomes
    ``data`` for batch/dense parallelism.  ``n_devices`` defaults to every
    local device and must be divisible by ``model_shards``.
    """
    n = n_devices if n_devices is not None else len(jax.devices())
    if model_shards < 1 or n % model_shards:
        raise ValueError(
            f"{n} devices not divisible into model={model_shards} shards"
        )
    return _make((n // model_shards, model_shards), ("data", "model"))
