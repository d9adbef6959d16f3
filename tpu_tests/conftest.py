"""Mosaic-compiled Pallas kernel tests — require a real TPU.

``tests/`` pins an 8-device virtual CPU mesh and exercises the Pallas
kernels only in interpret mode; this suite runs them through the actual
Mosaic compiler on the attached chip at production shapes (layouts, VMEM
budgets at the bench block size, the shard_map ``check_vma=False``
interaction).  It lives outside ``tests/`` because that conftest's CPU pin
applies at import to the whole pytest session.

Off-TPU the suite FAILS at collection (non-zero exit) instead of skipping:
a green run must mean the kernels compiled on the chip.

Each completed TPU session writes a ``bench_runs/`` provenance record
(device string, per-test outcomes, git SHA).
"""

import jax
import pytest

_DEVICE = jax.devices()[0]
if _DEVICE.platform != "tpu":
    pytest.exit(f"tpu_tests/ needs a TPU backend; JAX found "
                f"{_DEVICE.platform} ({_DEVICE.device_kind})", returncode=1)

from anomod.utils.platform import enable_compile_cache

enable_compile_cache()
_RESULTS = {}


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call":
        _RESULTS[item.name] = rep.outcome


def pytest_sessionfinish(session, exitstatus):
    if not _RESULTS:
        return
    from anomod.provenance import capture_record, write_capture
    n_passed = sum(1 for v in _RESULTS.values() if v == "passed")
    rec = capture_record(
        "tpu_kernel_parity", float(n_passed), "tests_passed",
        device=str(_DEVICE), n_tests=len(_RESULTS),
        outcomes=dict(sorted(_RESULTS.items())), exitstatus=int(exitstatus))
    write_capture(rec)
