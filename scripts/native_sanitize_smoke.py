#!/usr/bin/env python
"""Sanitizer smoke for the GIL-free native staging path.

The serve hot loop's staging (``anomod_stage_lanes`` /
``anomod_stage_lanes_mat`` + the shared ``Runtime`` pool, PR 7) runs
with the GIL released and multiple shard workers filling pinned scratch
concurrently — the repo's hardest-to-review code path.  This smoke
turns it into a CI-checkable artifact: it builds the whole native layer
with ``-fsanitize=thread`` (or ``address``) plus the staging hammer
driver (``native/sanitize_hammer.cpp`` — N worker threads, each owning
its own pipeline scratch slots, ALL sharing one Runtime pool: the
StagePlan fill pattern) and runs it.  The hammer also covers the
admission-plane columnar SFQ kernels (``anomod_sfq_drain`` /
``anomod_sfq_victim``): each worker drives them against an O(n^2)
repeated-scan reference oracle, so the serve drain/shed hot loop is
proven race-free and byte-identical the same way the staging layer is.

Why a native driver instead of the Python GIL-overlap hammer: a
TSan-instrumented shared library cannot be dlopen'd into an
uninstrumented CPython (the TSan runtime must own the process from
start), so the hammer drives the same ``extern "C"`` entry points with
the same concurrency shape and the same byte-parity oracle natively.

Verdicts (one JSON line on stdout):

- ``ok``   — built with the sanitizer, hammer ran clean; exit 0
- ``skip`` — toolchain cannot build sanitized binaries (no compiler,
  or ``-fsanitize`` probe failed); the REASON is recorded; exit 0
- ``fail`` — the sanitizer reported a race/error, or the hammer's
  byte-parity oracle failed; stderr carries the report; exit 1
"""

import argparse
import json
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
NATIVE = ROOT / "native"

_TARGETS = {"tsan": "anomod_hammer_tsan", "asan": "anomod_hammer_asan"}
_FLAGS = {"tsan": "thread", "asan": "address"}
_RUN_ENV = {"tsan": {"TSAN_OPTIONS": "halt_on_error=1 exitcode=66"},
            "asan": {"ASAN_OPTIONS": "halt_on_error=1"}}


def probe(sanitizer: str, cxx: str = None) -> dict:
    """Can this box build+link ``-fsanitize=<sanitizer>`` at all?
    Compiles a trivial threaded program with the SAME compiler command
    the Makefile will use (the full ``$CXX`` — e.g. ``ccache g++`` —
    default g++; probe and build must agree or a probe pass guarantees
    nothing); the reason string is what the SKIP verdict carries."""
    if cxx is None:
        import os
        cxx = (os.environ.get("CXX") or "").strip() or "g++"
    parts = cxx.split()
    if shutil.which(parts[0]) is None:
        return {"ok": False,
                "reason": f"no C++ compiler ({parts[0]}) on PATH"}
    if shutil.which("make") is None:
        return {"ok": False, "reason": "make not on PATH"}
    with tempfile.TemporaryDirectory() as td:
        src = Path(td) / "probe.cpp"
        src.write_text("#include <thread>\n"
                       "int main(){std::thread t([]{}); t.join();}\n")
        flag = _FLAGS.get(sanitizer, sanitizer)
        try:
            r = subprocess.run(
                [*parts, f"-fsanitize={flag}", "-pthread", str(src),
                 "-o", str(Path(td) / "probe")],
                capture_output=True, text=True, timeout=120)
        except subprocess.TimeoutExpired:
            return {"ok": False,
                    "reason": f"-fsanitize={flag} probe timed out"}
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().splitlines()[-1:]
        return {"ok": False,
                "reason": f"-fsanitize={flag} probe failed: "
                          f"{' '.join(tail) or 'compiler error'}"}
    return {"ok": True, "reason": ""}


def run(sanitizer: str = "tsan", workers: int = 4,
        iters: int = 40) -> dict:
    """Build + run the sanitized staging hammer; returns the verdict
    dict (never raises on the skip/fail paths — the caller maps
    status to its own exit policy)."""
    out = {"check": "native_sanitize_smoke", "sanitizer": sanitizer}
    if sanitizer not in _TARGETS:
        raise ValueError(f"unknown sanitizer {sanitizer!r}")
    p = probe(sanitizer)
    if not p["ok"]:
        out.update(status="skip", reason=p["reason"])
        return out
    target = _TARGETS[sanitizer]
    try:
        build = subprocess.run(["make", "-C", str(NATIVE), target],
                               capture_output=True, text=True,
                               timeout=300)
    except subprocess.TimeoutExpired:
        out.update(status="fail", reason="sanitized build timed out")
        return out
    if build.returncode != 0:
        # the probe proved the toolchain CAN build sanitized binaries,
        # so a failing hammer build is a real breakage (bad source /
        # Makefile), not a missing-sanitizer box — fail, don't skip
        out.update(status="fail",
                   reason="sanitized build failed (probe passed, so "
                          "this is a source/Makefile breakage, not a "
                          "toolchain gap)",
                   detail=build.stderr.strip()[-2000:])
        return out
    import os
    env = dict(os.environ)
    env.update(_RUN_ENV[sanitizer])
    try:
        r = subprocess.run([str(NATIVE / target), str(workers),
                            str(iters)], capture_output=True, text=True,
                           timeout=300, env=env)
    except subprocess.TimeoutExpired:
        # a deadlock is a typical sanitizer-era failure mode: the
        # verdict must still be a verdict (the gate prints ONE JSON
        # line and maps fail to its own exit code — never a traceback)
        out.update(status="fail",
                   reason="sanitized hammer timed out (possible "
                          "deadlock in the staging path)")
        return out
    out["exit_code"] = r.returncode
    if r.returncode == 0:
        out.update(status="ok", workers=workers, iters=iters)
    elif r.returncode == 2:
        out.update(status="fail", reason="byte-parity oracle failed "
                   "under the sanitized build")
    else:
        out.update(status="fail",
                   reason=f"{sanitizer} reported an error "
                          f"(exit {r.returncode})",
                   detail=r.stderr.strip()[-2000:])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sanitizer", choices=["tsan", "asan", "both"],
                    default="tsan")
    ap.add_argument("--workers", type=int, default=4,
                    help="concurrent staging worker threads")
    ap.add_argument("--iters", type=int, default=40,
                    help="staging calls per worker (small-slot pass; "
                         "the pool fan-out pass runs iters/8)")
    args = ap.parse_args(argv)
    legs = ["tsan", "asan"] if args.sanitizer == "both" \
        else [args.sanitizer]
    rc = 0
    for leg in legs:
        out = run(leg, workers=args.workers, iters=args.iters)
        print(json.dumps(out))
        if out["status"] == "fail":
            print(f"native_sanitize_smoke: {leg} FAILED — "
                  f"{out.get('reason')}", file=sys.stderr)
            if out.get("detail"):
                print(out["detail"], file=sys.stderr)
            rc = 1
        elif out["status"] == "skip":
            print(f"native_sanitize_smoke: {leg} SKIP — "
                  f"{out.get('reason')}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
