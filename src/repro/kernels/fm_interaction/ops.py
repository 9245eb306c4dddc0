"""Jit'd wrapper for the FM-interaction kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.contracts import contract
from repro.kernels.fm_interaction.kernel import fm_interaction_pallas


@contract(max_sort_size=0)
@jax.jit
def fm_interaction(v: jnp.ndarray) -> jnp.ndarray:
    return fm_interaction_pallas(v)
