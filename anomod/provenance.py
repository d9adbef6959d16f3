"""Machine-readable capture provenance.

Every quality sweep (``anomod stream --all``, ``anomod quality``) and
``tpu_tests`` session is written as one JSON file under ``bench_runs/`` so
the accuracy tables in ``docs/QUALITY.md`` cite committed, re-checkable
artifacts instead of prose: each record carries the measured value, the
kernel, the *device string*, jax/jaxlib versions, a UTC timestamp, and the
git SHA of the tree that produced it.  Speed is not recorded here: PERF.md
and ``PERF_LEDGER.jsonl`` hold every statement about speed.

Writes are best-effort: a benchmark must never fail because the repo is
read-only or git is absent, so all failures degrade to returning ``None``.
"""

from __future__ import annotations

import json
import os
import subprocess
import time
from typing import Optional

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "bench_runs")


def git_sha(cwd: Optional[str] = None) -> str:
    """Best-effort HEAD SHA of the benchmarked tree, suffixed ``-dirty``
    when the working tree has uncommitted changes — a record citing a
    clean SHA must actually be reproducible from it.  A copy without
    ``.git`` (a ``git archive`` checkout on the chip machine) names
    itself in a ``.source_tree`` file instead (``git write-tree >
    <copy>/.source_tree``), reported as ``tree:<hash>``; '' when there is
    neither."""
    cwd = cwd or os.path.dirname(DEFAULT_DIR)
    try:
        r = subprocess.run(["git", "rev-parse", "HEAD"], cwd=cwd,
                           capture_output=True, timeout=10)
        if r.returncode == 0:
            sha = r.stdout.decode().strip()
            # -uno: a capture record being written is itself untracked,
            # so counting untracked files would mark every capture dirty
            # by construction; only modified TRACKED files make the
            # measured code state unreproducible
            s = subprocess.run(["git", "status", "--porcelain", "-uno"],
                               cwd=cwd, capture_output=True, timeout=10)
            if s.returncode == 0 and s.stdout.strip():
                sha += "-dirty"
            return sha
    except Exception:
        pass                                   # no git binary: as no repo
    try:
        with open(os.path.join(cwd, ".source_tree")) as f:
            return "tree:" + f.read().strip()
    except OSError:
        return ""


def capture_record(metric: str, value: float, unit: str, **extra) -> dict:
    """Build a full provenance record for one measurement.

    ``extra`` carries measurement-specific fields (kernel, device, raw
    per-repeat wall times, workload shape...).  Environment fields are
    stamped here so every record is self-describing.
    """
    import jax
    rec = {
        "metric": metric,
        "value": value,
        "unit": unit,
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": git_sha(),
        "jax_version": jax.__version__,
    }
    try:
        import jaxlib
        rec["jaxlib_version"] = jaxlib.__version__
    except Exception:
        pass
    rec.update(extra)
    return rec


def write_capture(record: dict, outdir: Optional[str] = None) -> Optional[str]:
    """Write one capture record to ``bench_runs/``; return its path.

    Filename encodes timestamp + metric + device class so a directory
    listing reads as a capture log.  Returns None (never raises) when the
    filesystem refuses — provenance must not break the measurement.
    """
    outdir = outdir or os.environ.get("ANOMOD_BENCH_RUNS_DIR", DEFAULT_DIR)
    try:
        os.makedirs(outdir, exist_ok=True)
        device = str(record.get("device", "unknown"))
        devclass = "tpu" if "TPU" in device.upper() else \
            ("cpu" if "CPU" in device.upper() else "dev")
        ts = record.get("timestamp_utc", "").replace(":", "").replace("-", "")
        stem = f"{ts}_{record.get('metric', 'capture')}_{devclass}"
        # O_EXCL + counter suffix: two captures of the same metric within
        # one second must not clobber each other — the log's whole job is
        # to preserve every capture.
        for i in range(1000):
            path = os.path.join(
                outdir, f"{stem}.json" if i == 0 else f"{stem}_{i}.json")
            try:
                fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
            except FileExistsError:
                continue
            with os.fdopen(fd, "w") as f:
                json.dump(record, f, indent=1, sort_keys=True)
                f.write("\n")
            return path
        return None
    except Exception:
        return None
