"""Backend dispatch: the BASELINE.json ``backend={cpu, jax-tpu}`` switch.

``cpu`` = pure numpy (the correctness oracle); ``jax`` / ``jax-tpu`` = jax.numpy
on whatever platform JAX selected (CPU mesh in tests, the real chip
otherwise).  Numeric modules take an ``xp`` array namespace so the same expression
tree runs on either; JAX-only paths (jit/pallas) live in anomod.ops and
anomod.models and are reached when backend != cpu.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from anomod.config import get_config

_JAX_BACKENDS = ("jax", "jax-tpu", "tpu")


def resolve(backend: str | None = None) -> str:
    b = backend or get_config().backend
    return "jax" if b in _JAX_BACKENDS else "cpu"


def xp(backend: str | None = None) -> Any:
    """Array namespace for the chosen backend."""
    if resolve(backend) == "jax":
        import jax.numpy as jnp
        return jnp
    return np


def to_host(arr: Any) -> np.ndarray:
    return np.asarray(arr)


def device_put(arr: np.ndarray, backend: str | None = None) -> Any:
    if resolve(backend) == "jax":
        import jax
        return jax.device_put(arr)
    return arr
