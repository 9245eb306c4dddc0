"""The control and the planted faults that the comparison must refuse.

Each is a ``model_override`` for ``families.dlrm.build``: it takes the built
program model and returns one whose timed path is broken in one way.  Only
``calibrate.py`` and the tests use them; a benchmark run never does.

* ``control``: the program's own lower-precision path, bfloat16 compute
  (``Dtypes(compute=bfloat16)``) under the float32 parameters.
* ``frozen``: the step returns the state it was given.
* ``half_batch``: the loss is the mean over the first half of the batch.
* ``no_exchange`` (sharded configurations): lookups reach only shard 0's
  rows; the other shards' rows come back as zeros.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Iterator

import jax.numpy as jnp

__all__ = ["OVERRIDES", "control", "frozen", "half_batch", "no_exchange"]


class _Proxy:
    """The model with one method replaced."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)


def control(model):
    from repro.models.dlrm import DLRM
    from repro.nn.layers import Dtypes

    dt = Dtypes(param=model.cfg.dtypes.param, compute=jnp.bfloat16)
    return DLRM(dataclasses.replace(model.cfg, dtypes=dt))


class _Frozen(_Proxy):
    def train_step(self, state, batch):
        _, metrics = self._model.train_step(state, batch)
        return state, metrics


def frozen(model):
    return _Frozen(model)


class _HalfBatch(_Proxy):
    def train_step(self, state, batch):
        from repro.models.common import bce_with_logits

        half = batch["label"].shape[0] // 2
        step = dataclasses.replace(
            self._model._train_step(),
            loss=lambda logits, labels: bce_with_logits(logits[:half], labels[:half]))
        return step(state, batch)


def half_batch(model):
    return _HalfBatch(model)


@contextlib.contextmanager
def no_exchange() -> Iterator[None]:
    """While open, the sharded gather serves shard 0's rows only."""
    from repro.core import sharded

    orig = sharded._partitioned_take

    def local_only(w, owner, slot):
        return orig(w, jnp.where(owner == 0, owner, w.shape[0]), slot)

    sharded._partitioned_take = local_only
    try:
        yield
    finally:
        sharded._partitioned_take = orig


# name -> (model override or None, context the run is made under)
OVERRIDES: dict[str, tuple[Callable[[Any], Any] | None, Callable[[], Any]]] = {
    "control": (control, contextlib.nullcontext),
    "frozen": (frozen, contextlib.nullcontext),
    "half_batch": (half_batch, contextlib.nullcontext),
    "no_exchange": (None, no_exchange),
}
