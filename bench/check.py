"""The comparison that decides ``correct`` for a training cell.

Each number named in the configuration file's ``check`` is held against its
limit there; ``readings`` works out these:

* ``loss_gap``: over the compared steps, the largest ``|L_prog - L_ref| /
  |L_ref|``.
* ``grad_gap``: the first gradient as the optimizer got it, per leaf.  The
  program's is worked out from its state after one step, ``(p0 - p1) / lr``
  (plain SGD), the reference's is its own gradient.  The number is the worst
  leaf's gap of norms, ``| |g_prog| - |g_ref| |``, over the larger of the
  reference's norm of that leaf and of the median leaf.  Recovering a
  gradient from two float32 states cancels most of its digits where the
  update is small beside the weight, so on the chip this number reads about
  1e-3 for sound runs as for the control: it is reported, not compared.
* ``change_gap``: the same for the change of the parameters over the compared
  steps, ``p_n - p0``.

Leaves whose reference gradient is under a thousandth of the median leaf's
are nought to rounding and are left out of both norms' comparisons (the
rule is on the reference's gradient, never on a leaf's name).
"""
from __future__ import annotations

from typing import Dict, Mapping, Sequence

import numpy as np

__all__ = ["NEGLIGIBLE", "readings", "judge"]

NEGLIGIBLE = 1e-3  # of the median leaf's reference gradient norm


def _norm(x: np.ndarray) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _worst(prog: Mapping[str, float], ref: Mapping[str, float], keep: Sequence[str]):
    med = float(np.median([ref[n] for n in keep]))
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in keep}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def readings(ref: Mapping, losses: Sequence[float], p1: Mapping[str, np.ndarray],
             pn: Mapping[str, np.ndarray], lr: float) -> Dict[str, object]:
    """``ref`` is the reference's ``run`` result; ``losses`` the program's
    losses of the compared steps; ``p1`` / ``pn`` its leaves after the first
    and after the last compared step."""
    names = list(ref["g1"])
    g_ref = {n: _norm(ref["g1"][n]) for n in names}
    med = float(np.median(list(g_ref.values())))
    keep = [n for n in names if g_ref[n] >= NEGLIGIBLE * med]
    g_prog = {n: _norm(ref["p0"][n] - p1[n]) / lr for n in names}
    d_ref = {n: _norm(ref["d"][n]) for n in names}
    d_prog = {n: _norm(pn[n] - ref["p0"][n]) for n in names}
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(losses, ref["losses"]))
    grad_gap, grad_leaf = _worst(g_prog, g_ref, keep)
    change_gap, change_leaf = _worst(d_prog, d_ref, keep)
    return {
        "loss_gap": loss_gap,
        "grad_gap": grad_gap,
        "change_gap": change_gap,
        "detail": {
            "grad_gap": grad_gap,
            "losses_prog": list(losses), "losses_ref": list(ref["losses"]),
            "grad_worst_leaf": grad_leaf, "change_worst_leaf": change_leaf,
            "left_out": sorted(set(names) - set(keep)),
        },
    }


def judge(read: Mapping[str, object], limits: Mapping[str, float]) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit; a number that is not finite
    fails."""
    return {k: {"value": float(read[k]), "limit": float(v)} for k, v in limits.items()}


def passed(judged: Mapping[str, Mapping[str, float]]) -> bool:
    return all(np.isfinite(j["value"]) and j["value"] <= j["limit"] for j in judged.values())
