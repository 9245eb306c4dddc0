"""The one traffic generator: Zipf-skewed sparse batches, optionally drifting.

A traffic mix is a JSON file of this module's parameters
(``traffic/<mix>.json``).  The arithmetic is a copy of the program's
``repro.data.synth.sparse_batch`` / ``DriftingZipfSpec`` (tests check the two
agree bit for bit), kept here so that no program change can move the
yardstick.  Batch ``step`` is a pure function of ``(seed, step)``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Sequence

import numpy as np

__all__ = ["Mix", "load_mix", "sparse_batch"]


@dataclasses.dataclass(frozen=True)
class Mix:
    """Parameters of one traffic mix (the keys of ``traffic/<mix>.json``)."""

    zipf_a: float = 1.2  # per-field popularity exponent; id == popularity rank
    drift_every: int = 0  # 0 = stationary; N = the hot set rotates every N steps
    shift_fraction: float = 0.0  # rotation per phase, as a share of each vocab
    phase_offset: int = 0  # step s lies in phase (s + phase_offset) // drift_every

    def shifts(self, vocab_sizes: Sequence[int], step: int) -> np.ndarray | None:
        """Per-field id rotation of the phase holding ``step`` (None when
        stationary)."""
        if not self.drift_every:
            return None
        vocabs = np.asarray(vocab_sizes, dtype=np.int64)
        per_phase = np.maximum((self.shift_fraction * vocabs).astype(np.int64), 1)
        return (((step + self.phase_offset) // self.drift_every) * per_phase) % vocabs


def load_mix(path: Path) -> Mix:
    raw = json.loads(Path(path).read_text())
    unknown = set(raw) - {f.name for f in dataclasses.fields(Mix)}
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    return Mix(**raw)


def _zipf_ids(rng: np.random.Generator, vocab: int, size, a: float) -> np.ndarray:
    """Zipf over [0, vocab) by inverse CDF: rank r has p ~ (r + 1)^-a."""
    u = rng.random(size)
    if a == 1.0:
        ids = np.exp(u * np.log(vocab)) - 1.0
    else:
        h = (vocab ** (1.0 - a) - 1.0) / (1.0 - a)
        ids = ((u * h * (1.0 - a)) + 1.0) ** (1.0 / (1.0 - a)) - 1.0
    return np.clip(ids.astype(np.int64), 0, vocab - 1)


def sparse_batch(
    mix: Mix, vocab_sizes: Sequence[int], n_dense: int, batch: int, seed: int, step: int
) -> Dict[str, np.ndarray]:
    """One id per field (``sparse`` int32 [batch, fields]), ``dense`` float32
    features and a 0/1 ``label`` that depends on the ids."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    f = len(vocab_sizes)
    sparse = np.stack(
        [_zipf_ids(rng, v, batch, mix.zipf_a) for v in vocab_sizes], axis=1
    ).astype(np.int32)
    shift = mix.shifts(vocab_sizes, step)
    if shift is not None:
        vocabs = np.asarray(vocab_sizes, dtype=np.int64)
        sparse = ((sparse.astype(np.int64) + shift) % vocabs).astype(np.int32)
    out: Dict[str, np.ndarray] = {"sparse": sparse}
    if n_dense:
        out["dense"] = rng.normal(size=(batch, n_dense)).astype(np.float32)
    h = ((sparse * np.arange(1, f + 1)) % 97).sum(1) / (97.0 * f)
    noise = rng.normal(scale=0.3, size=batch)
    out["label"] = ((h + noise) > 0.5).astype(np.float32)
    return out


def seed_key(seed: int) -> np.ndarray:
    """The raw ``uint32[2]`` PRNG key of a run: the weights of both the
    program and the reference are drawn from it.  ``SeedSequence`` takes any
    non-negative integer, so seeds past 32 bits give distinct keys."""
    return np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
