"""Plain DLRM reference for the ``dlrm-*`` configurations.

Straightforward ``jax.numpy``: one uncached embedding table per field,
bottom MLP (ReLU on every layer), pairwise-dot interaction of the bottom
output with the field embeddings, top MLP (ReLU but on the last layer),
mean binary cross-entropy on the logits, plain SGD on every parameter at the
configuration's learning rate.  It imports nothing of the program and takes
nothing the program made: its weights are drawn here from the run's seed,
following the configuration's stated initialisation (``init`` below), and
only the rows the compared steps touch are drawn, so the reference fits
beside nothing else on one chip.

Matrix products run at JAX's default precision, as the configuration states
(float32 parameters and compute, no precision override).
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

__all__ = ["dense_init", "draw_rows", "forward", "run", "touched_ids", "leaf_names"]


def _uniform(key, shape, bound: float):
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _mlp_init(key, dims: Sequence[int]):
    """One ``(w, b)`` per layer: w ~ U(+-1/sqrt(fan_in)), b = 0; layer i takes
    the i-th of ``split(key, layers)`` and draws w from the first half of
    ``split`` of that."""
    keys = jax.random.split(key, len(dims) - 1)
    out = []
    for k, d_in, d_out in zip(keys, dims[:-1], dims[1:]):
        kw, _ = jax.random.split(k)
        out.append((_uniform(kw, (d_in, d_out), 1.0 / np.sqrt(max(d_in, 1))),
                    jnp.zeros((d_out,), jnp.float32)))
    return out


def _top_in(cfg: Mapping) -> int:
    f = len(cfg["vocab_sizes"]) + 1
    return cfg["embed_dim"] + f * (f - 1) // 2


def dense_init(cfg: Mapping, key) -> Dict[str, list]:
    """Bottom and top MLPs from the run key: ``(k_emb, k_bot, k_top) =
    split(key, 3)``."""
    _, k_bot, k_top = jax.random.split(key, 3)
    return {
        "bottom": _mlp_init(k_bot, (cfg["n_dense"],) + tuple(cfg["bottom_mlp"])),
        "top": _mlp_init(k_top, (_top_in(cfg),) + tuple(cfg["top_mlp"]) + (1,)),
    }


def table_key(key):
    """Key of the embedding draw: the one split of ``k_emb``."""
    k_emb = jax.random.split(key, 3)[0]
    return jax.random.split(k_emb, 1)[0]


def _counters(rows: jnp.ndarray, dim: int):
    """High and low uint32 words of the 64-bit element index ``r * dim + j``,
    built from 16-bit halves of r (no 64-bit integers under jit)."""
    r = rows.astype(jnp.uint32)[:, None]
    j = jnp.arange(dim, dtype=jnp.uint32)[None, :]
    d = jnp.uint32(dim)
    low = (r & 0xFFFF) * d  # < 2^32
    high = (r >> 16) * d  # index = high * 2^16 + low + j
    shifted = (high & 0xFFFF) << 16
    s = shifted + low
    lo = s + j
    hi = (high >> 16) + (s < shifted).astype(jnp.uint32) + (lo < s).astype(jnp.uint32)
    return hi, lo


def draw_rows(key, rows: jnp.ndarray, dim: int, bound: float) -> jnp.ndarray:
    """Rows ``rows`` of ``jax.random.uniform(key, (vocab, dim), float32,
    -bound, bound)`` under the partitionable threefry: element (r, j) comes
    from the counter ``r * dim + j`` alone, so the rows are drawn without the
    rest of the table."""
    hi, lo = _counters(rows, dim)
    b1, b2 = threefry2x32_p.bind(key[0], key[1], hi, lo)
    one = jnp.asarray(np.array(1.0, np.float32).view(np.uint32))
    unit = jax.lax.bitcast_convert_type(((b1 ^ b2) >> 9) | one, jnp.float32) - 1.0
    lo_v, hi_v = jnp.float32(-bound), jnp.float32(bound)
    return jnp.maximum(lo_v, unit * (hi_v - lo_v) + lo_v)


def _mlp(layers, x, final_act: bool, dtype):
    for i, (w, b) in enumerate(layers):
        x = x.astype(dtype) @ w.astype(dtype) + b.astype(dtype)
        if i < len(layers) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def forward(params, emb: jnp.ndarray, dense: jnp.ndarray, dtype=jnp.float32):
    """Logits [B] from embeddings [B, F, D] and dense features [B, n_dense]."""
    d = _mlp(params["bottom"], dense, True, dtype)
    z = jnp.concatenate([d[:, None, :].astype(emb.dtype), emb], axis=1)
    zz = jnp.einsum("bfd,bgd->bfg", z, z)
    iu, ju = np.triu_indices(z.shape[1], k=1)
    x = jnp.concatenate([d.astype(zz.dtype), zz[:, iu, ju]], axis=-1)
    return _mlp(params["top"], x, False, dtype)[:, 0]


def bce(logits, labels):
    z = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z))))


def touched_ids(batches: Sequence[Mapping[str, np.ndarray]], n_fields: int) -> List[np.ndarray]:
    """Per field, the sorted distinct ids the given batches use."""
    return [np.unique(np.concatenate([b["sparse"][:, f] for b in batches]))
            for f in range(n_fields)]


def leaf_names(cfg: Mapping) -> List[str]:
    """Names of the compared leaves: every MLP weight and bias, then one
    embedding table per field."""
    out = []
    for tower, n in (("bottom", len(cfg["bottom_mlp"])), ("top", len(cfg["top_mlp"]) + 1)):
        for i in range(n):
            out += [f"{tower}.l{i}.w", f"{tower}.l{i}.b"]
    return out + [f"table.f{i}" for i in range(len(cfg["vocab_sizes"]))]


def _flat(params) -> List:
    return [x for tower in ("bottom", "top") for wb in params[tower] for x in wb]


def run(cfg: Mapping, key: np.ndarray, batches: Sequence[Mapping[str, np.ndarray]],
        steps: int = 3, dtype=jnp.float32) -> Dict[str, object]:
    """``steps`` SGD steps of the plain model on ``batches`` from the run key.

    Returns numpy: ``losses`` [steps], and per leaf (``leaf_names`` order)
    ``p0`` (initial values), ``g1`` (first gradient) and ``d`` (change after
    ``steps``).  Table leaves hold the rows of ``touched_ids(batches)``."""
    key = jnp.asarray(key, jnp.uint32)
    vocabs = list(cfg["vocab_sizes"])
    dim = cfg["embed_dim"]
    ids = touched_ids(batches[:steps], len(vocabs))
    offsets = np.concatenate([[0], np.cumsum(vocabs)[:-1]]).astype(np.int64)
    # one compact table holds every touched row: field f owns a block of
    # min(vocab, steps x batch) rows, so the shapes, and the compiled
    # programs, are the same for every seed; lane (b, f) reads row base[f] +
    # the position of its id among field f's touched ids
    widths = [min(int(v), steps * int(cfg["batch_size"])) for v in vocabs]
    base = np.concatenate([[0], np.cumsum(widths)[:-1]]).astype(np.int64)
    rows = np.concatenate([np.pad(u + o, (0, w - len(u)), constant_values=o)
                           for u, o, w in zip(ids, offsets, widths)])
    table0 = jax.jit(draw_rows, static_argnums=(2, 3))(
        table_key(key), jnp.asarray(rows, jnp.int32), dim, float(1.0 / np.sqrt(dim)))
    params0 = jax.jit(lambda k: dense_init(cfg, k))(key)
    lr = float(cfg["lr"])

    def loss_fn(params, table, lanes, dense, label):
        emb = jnp.take(table, lanes, axis=0).astype(dtype)
        return bce(forward(params, emb, dense, dtype), label)

    @jax.jit
    def step(params, table, lanes, dense, label):
        loss, (gp, gt) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, table, lanes, dense, label)
        new_p = jax.tree_util.tree_map(lambda p, g: p - lr * g.astype(jnp.float32), params, gp)
        return new_p, table - lr * gt.astype(jnp.float32), loss, gp, gt

    params, table = params0, table0
    losses, g1 = [], None
    for s in range(steps):
        b = batches[s]
        lanes = np.stack([base[f] + np.searchsorted(ids[f], b["sparse"][:, f])
                          for f in range(len(vocabs))], axis=1).astype(np.int32)
        params, table, loss, gp, gt = step(params, table, lanes, b["dense"], b["label"])
        losses.append(float(loss))
        if s == 0:
            g1 = (_flat(gp), gt)

    def split_rows(t):
        t = np.asarray(t)
        return [t[o:o + len(u)] for o, u in zip(base, ids)]

    p0 = [np.asarray(x) for x in _flat(params0)] + split_rows(table0)
    g = [np.asarray(x) for x in g1[0]] + split_rows(g1[1])
    pn = [np.asarray(x) for x in _flat(params)] + split_rows(table)
    names = leaf_names(cfg)
    return {"losses": losses, "ids": ids,
            "p0": dict(zip(names, p0)), "g1": dict(zip(names, g)),
            "d": {n: b - a for n, a, b in zip(names, p0, pn)}}
