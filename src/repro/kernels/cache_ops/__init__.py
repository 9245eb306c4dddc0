"""Cache hot-path kernels (ROADMAP item 3): bounded top-K victim selection,
fused dedup -> residency-probe -> slot-assign, fused arena gather+decode.

``ops`` holds the dispatching entry points (Pallas on accelerators,
bit-identical XLA references on CPU); ``ref`` the XLA implementations;
``kernel`` the Pallas bodies (compiled on TPU, interpreted on CPU).
"""
from repro.kernels.cache_ops.ops import (
    arena_gather,
    chunked_move,
    kernels_enabled,
    plan_image,
    shard_bucketize,
    victim_topk,
)
from repro.kernels.cache_ops.ref import PlanImage

__all__ = [
    "PlanImage",
    "arena_gather",
    "chunked_move",
    "kernels_enabled",
    "plan_image",
    "shard_bucketize",
    "victim_topk",
]
