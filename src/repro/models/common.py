"""Shared model scaffolding: losses, metrics, the cached-embedding train-step
pattern (prepare -> diff gather -> synchronous row update).

``CollectionTrainStep`` is the collection-era pattern every recsys model
uses: a ``FeatureBatch`` goes through ``EmbeddingCollection.prepare`` outside
the grad closure, the loss differentiates w.r.t. ``collection.weights`` (the
fast tiers), and ``apply_grads`` performs the synchronous row update.
``EmbTrainStep`` is the legacy single-arena variant kept for the
``cached_embedding`` adapter path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.contracts import INT_COUNTERS, contract
from repro.core import cached_embedding as ce
from repro.core.collection import EmbeddingCollection, FeatureBatch
from repro.obs import tracing as scopes
from repro.optim.optimizers import Optimizer

__all__ = [
    "bce_with_logits",
    "softmax_xent",
    "auc_proxy",
    "flush_embeddings",
    "EmbTrainStep",
    "CollectionTrainStep",
    "CollectionModelMixin",
]


def flush_embeddings(collection: "EmbeddingCollection", state: Dict[str, Any]) -> Dict[str, Any]:
    """The shared pre-checkpoint barrier: flush every cached slab under the
    ``emb`` key (models expose this as ``model.flush``)."""
    return dict(state, emb=collection.flush(state["emb"]))


def bce_with_logits(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    z = logits.astype(jnp.float32)
    y = labels.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def auc_proxy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Fast pairwise-ranking AUC estimate (exact when no score ties)."""
    s = logits.astype(jnp.float32).reshape(-1)
    y = labels.astype(jnp.float32).reshape(-1)
    order = jnp.argsort(s)
    ranks = jnp.zeros_like(s).at[order].set(jnp.arange(1, s.size + 1, dtype=jnp.float32))
    n_pos = jnp.sum(y)
    n_neg = y.size - n_pos
    auc = (jnp.sum(ranks * y) - n_pos * (n_pos + 1) / 2) / jnp.maximum(n_pos * n_neg, 1.0)
    return jnp.where((n_pos > 0) & (n_neg > 0), auc, 0.5)


@dataclasses.dataclass(frozen=True)
class EmbTrainStep:
    """Builds the jittable cached-embedding train step shared by all recsys archs.

    ``fwd(dense_params, emb_rows, batch) -> (logits, aux_dict)`` where
    ``emb_rows = gather(cached_weight, slots)`` happens inside so gradients
    reach the cached rows.
    """

    emb_cfg: ce.CachedEmbeddingConfig
    optimizer: Optimizer
    collect_ids: Callable[[Dict[str, jnp.ndarray]], jnp.ndarray]  # batch -> flat global ids
    fwd: Callable[..., Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]
    loss: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray] = bce_with_logits
    emb_lr: float = 0.05

    def __call__(self, state: Dict[str, Any], batch: Dict[str, jnp.ndarray]):
        ids = self.collect_ids(batch)  # [ids_per_step] int32 global ids (-1 pad)
        emb_state, slots = ce.prepare_ids(self.emb_cfg, state["emb"], ids)

        def loss_fn(dense_params, cached_w):
            safe = jnp.where(slots >= 0, slots, cached_w.shape[0])  # negatives wrap
            rows = jnp.take(cached_w, safe, axis=0, mode="fill", fill_value=0)
            logits, aux = self.fwd(dense_params, rows, batch)
            return self.loss(logits, batch["label"]), (logits, aux)

        (loss_val, (logits, aux)), grads = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
            state["params"], emb_state.cache.cached_rows["weight"]
        )
        params, opt_state = self.optimizer.update(
            grads[0], state["opt"], state["params"], state["step"]
        )
        emb_state = ce.apply_row_grads(self.emb_cfg, emb_state, grads[1], self.emb_lr)
        metrics = {
            "loss": loss_val,
            "auc": auc_proxy(logits, batch["label"]),
            "hit_rate": emb_state.cache.hit_rate(),
            "cache_misses": emb_state.cache.misses,
            "uniq_overflows": emb_state.cache.uniq_overflows,
            **aux,
        }
        new_state = dict(state, params=params, opt=opt_state, emb=emb_state, step=state["step"] + 1)
        return new_state, metrics


@dataclasses.dataclass(frozen=True)
class CollectionTrainStep:
    """Jittable train step over an ``EmbeddingCollection``.

    ``features(batch) -> FeatureBatch`` replaces the hand-flattened
    ``collect_ids``; ``fwd(dense_params, rows, batch) -> (logits, aux)``
    receives the keyed gather output (feature name -> [.., dim] rows) so
    gradients reach the fast-tier weights of every slab — DEVICE tables and
    cached arenas alike.

    The step is exposed both fused (``__call__``) and split into the three
    pipeline stages (``plan_step`` / ``apply_step`` / ``compute_step``) so a
    pipelined trainer can dispatch step t+1's planning — which reads only ids
    and cache index state — while step t's dense compute is still running.
    ``__call__`` is exactly their composition, so the serial path stays the
    bit-exactness oracle for the pipelined one.

    Each stage runs under its layer's ``jax.named_scope`` (``cache.plan``,
    ``cache.movement``, ``cache.lookup``, ``dense``, ``cache.row_update``,
    ``telemetry``; see ``repro.obs.tracing.STEP_SCOPES``), set here, so the
    fused and the split stages carry the same names in a profiler trace
    (the nested ``telemetry`` hit probe and ``shard.exchange`` come from
    ``core.cache`` and ``core.sharded``).  Scopes are HLO metadata only.
    """

    collection: EmbeddingCollection
    optimizer: Optimizer
    features: Callable[[Dict[str, jnp.ndarray]], FeatureBatch]
    fwd: Callable[..., Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]
    loss: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray] = bce_with_logits
    emb_lr: float = 0.05

    def plan_step(
        self,
        state: Dict[str, Any],
        batch: Dict[str, jnp.ndarray],
        future_batches: Tuple[Dict[str, jnp.ndarray], ...] = (),
    ):
        """Weight-free planning half: dedup + slot assignment + movement plan
        for ``batch``, with ``future_batches``' ids merged as a lookahead
        window (their rows are prefetched and pinned; see
        ``EmbeddingCollection.plan_prepare``)."""
        fut = tuple(self.features(b) for b in future_batches)
        with jax.named_scope(scopes.SCOPE_PLAN):
            return self.collection.plan_prepare(
                state["emb"], self.features(batch), fb_future=fut
            )

    def apply_step(self, state: Dict[str, Any], plan) -> Dict[str, Any]:
        """Execute a plan's row movement (the only prepare half that touches
        weights — run it after the previous step's row update)."""
        with jax.named_scope(scopes.SCOPE_MOVEMENT):
            return dict(state, emb=self.collection.apply_plan(state["emb"], plan))

    # max_sort_size admits the batch-sized ``auc_proxy`` argsort at the
    # analysis.smoke batch of 32, nothing capacity-sized.
    @contract(donates=("state",), int_counters=INT_COUNTERS, max_sort_size=64)
    def compute_step(
        self,
        state: Dict[str, Any],
        batch: Dict[str, jnp.ndarray],
        addresses: Dict[str, jnp.ndarray],
    ):
        """Dense fwd/bwd + optimizer + synchronous row update, given the
        addresses planned for ``batch`` (whose rows are already resident)."""
        fb = self.features(batch)
        emb_state = state["emb"]

        def loss_fn(dense_params, emb_weights):
            with jax.named_scope(scopes.SCOPE_LOOKUP):
                rows = self.collection.gather(emb_weights, addresses, fb)
            with jax.named_scope(scopes.SCOPE_DENSE):
                logits, aux = self.fwd(dense_params, rows, batch)
                return self.loss(logits, batch["label"]), (logits, aux)

        # one named scope per layer (``repro.obs.tracing.STEP_SCOPES``): the
        # backward of each scope's ops keeps its name (``transpose(jvp(..))``)
        (loss_val, (logits, aux)), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True
        )(state["params"], self.collection.weights(emb_state))
        with jax.named_scope(scopes.SCOPE_DENSE):
            params, opt_state = self.optimizer.update(
                grads[0], state["opt"], state["params"], state["step"]
            )
            step = state["step"] + 1
        with jax.named_scope(scopes.SCOPE_ROW_UPDATE):
            emb_state = self.collection.apply_grads(emb_state, grads[1], self.emb_lr)
        with jax.named_scope(scopes.SCOPE_TELEMETRY):
            metrics = {
                "loss": loss_val,
                "auc": auc_proxy(logits, batch["label"]),
                **self.collection.metrics(emb_state),
                **aux,
            }
        new_state = dict(state, params=params, opt=opt_state, emb=emb_state, step=step)
        return new_state, metrics

    def __call__(self, state: Dict[str, Any], batch: Dict[str, jnp.ndarray]):
        plan = self.plan_step(state, batch)
        state = self.apply_step(state, plan)
        return self.compute_step(state, batch, plan.addresses)


class CollectionModelMixin:
    """The step surface shared by every model whose embeddings live in an
    ``EmbeddingCollection`` (expects ``self.collection`` / ``self.optimizer``
    / ``self.features`` / ``self.fwd`` and an embedding LR at ``cfg.lr``):
    the fused ``train_step`` plus the split pipeline stages ``plan_step`` /
    ``apply_step`` / ``compute_step`` consumed by ``PipelinedTrainer`` —
    planning is weight-free, so the trainer dispatches step t+1's plan while
    step t's dense compute runs."""

    @property
    def emb_lr(self) -> float:
        return self.cfg.lr

    def _train_step(self) -> CollectionTrainStep:
        return CollectionTrainStep(
            collection=self.collection,
            optimizer=self.optimizer,
            features=self.features,
            fwd=self.fwd,
            emb_lr=self.emb_lr,
        )

    def train_step(self, state, batch):
        return self._train_step()(state, batch)

    def plan_step(self, state, batch, future_batches=()):
        return self._train_step().plan_step(state, batch, future_batches)

    def apply_step(self, state, plan):
        return self._train_step().apply_step(state, plan)

    def compute_step(self, state, batch, addresses):
        return self._train_step().compute_step(state, batch, addresses)

    def refresh(self, state, cfg=None, writeback: bool = True):
        """Adaptive frequency refresh: re-rank the collection's cached slabs
        from their online decayed counters (``EmbeddingCollection.refresh``).
        Host-side and pure reindexing — call between steps (the trainers wire
        this as ``refresh_fn`` under ``TrainerConfig.refresh_interval``; serve
        passes ``writeback=False`` for its read-only cache states)."""
        new_emb, _ = self.collection.refresh(
            state["emb"], cfg, writeback=writeback
        )
        return dict(state, emb=new_emb)
