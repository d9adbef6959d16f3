"""Test config: pin JAX to a virtual 8-device CPU mesh (no TPU needed)."""

from anomod.utils.platform import enable_compile_cache, pin_cpu

pin_cpu(8)

import jax

# The suite's wall time is XLA:CPU *compile* time (the computations
# themselves are tiny).  Skipping the expensive HLO optimization passes
# cuts the full run by about a third with all numeric assertions intact —
# tests verify semantics against numpy oracles, not codegen.
# Optimized-pipeline behavior is exercised where it matters: tpu_tests/
# and chip_smoke.py (compiled on the real chip) never load this conftest.
jax.config.update("jax_disable_most_optimizations", True)

# The suite re-JITs the same train/replay computations every run; the
# persistent compilation cache (placed by the one shared rule) makes
# re-runs in the same workspace hit warm.
enable_compile_cache()


def make_qkv(L, H, D, seed=0):
    """Shared random q/k/v blocks for the sequence-parallel attention tests
    (one generator so cross-plane equivalence tests compare identical
    tensors)."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(L, H, D)).astype(np.float32))
                 for _ in range(3))
