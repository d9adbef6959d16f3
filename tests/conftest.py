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


#: Assertions of the benchmark's own test files that a later, accepted way
#: of growing the benchmark has overtaken.  Those files are the
#: benchmark's (``BENCHMARK.json`` ``paths``) and only a ``benchmark`` PR
#: may edit them, so the overtaken test is expected to fail here, by name
#: and with its reason, until one does (PERF.md section 7, item 5); what
#: else it asserted is asserted again beside the new cell's tests.
OVERTAKEN = {
    "tests/benchmark/test_benchmark_k2_cell.py::"
    "test_benchmark_json_is_sound_and_the_config_keeps_published_widths":
        "pins served_spans_per_s.workloads to the two cells of PR 28; a "
        "new cell that reports the metric appends its name to that list "
        "(PR 34: n3s-fleet-overload); the K2 widths it also checks are "
        "checked in tests/benchmark/test_benchmark_n3s_cell.py",
    "tests/benchmark/test_benchmark_n3s_cell.py::"
    "test_no_metric_of_the_new_cell_is_due_in_an_older_cell":
        "pins served_spans_per_s.workloads to the three cells of PR 34; "
        "PR 36 appends lxs2-fleet-overload to that list; everything else "
        "it asserts (the 25 n3s metrics list n3s alone, what each older "
        "cell reports) is asserted again by the test of the same name in "
        "tests/benchmark/test_benchmark_lxs2_cell.py",
    "tests/benchmark/test_benchmark_lxs2_cell.py::"
    "test_no_metric_of_the_new_cell_is_due_in_an_older_cell":
        "pins every cell's due per-layer list to the 99 entries of PR 36 "
        "and has each of a model cell's metrics list that cell alone; "
        "PR 38 appends sixteen that each list the model cells they are "
        "for (no twin a cell); what it asserts of the 99 is asserted "
        "again in tests/benchmark/test_benchmark_step_account.py",
}


def pytest_collection_modifyitems(config, items):
    import pytest
    for item in items:
        reason = OVERTAKEN.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
