"""ctypes bindings for the C++ native runtime (native/libanomod_native.so).

Builds on first use if the shared object is missing (g++ is baked into the
image); every entry point has a pure-Python fallback so the package works
without a toolchain.
"""

from __future__ import annotations

import ctypes
import subprocess
import warnings
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_SO_PATH = _NATIVE_DIR / "libanomod_native.so"
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
#: why the native runtime is unusable (build/load/symbol failure detail),
#: None while it is fine — surfaced by :func:`status` into
#: ``anomod validate`` and ``chip_smoke.py``, and quoted by the
#: ANOMOD_NATIVE=on refusal so the operator sees the root cause instead
#: of a silent slow path
_BUILD_ERROR: Optional[str] = None


def _stale() -> bool:
    """True when the .so is missing or older than any native source."""
    if not _SO_PATH.exists():
        return True
    so_mtime = _SO_PATH.stat().st_mtime
    srcs = [_NATIVE_DIR / "Makefile", *_NATIVE_DIR.glob("*.cpp"),
            *_NATIVE_DIR.glob("*.h")]
    return any(s.exists() and s.stat().st_mtime > so_mtime for s in srcs)


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED, _BUILD_ERROR
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    # Only shell out to make when the .so is actually stale (mtime check):
    # read-only installs and toolchain-free hosts then skip the subprocess
    # spawn entirely, and a failed build degrades observably, not silently.
    if (_NATIVE_DIR / "Makefile").exists() and _stale():
        try:
            subprocess.run(["make", "-C", str(_NATIVE_DIR)], check=True,
                           capture_output=True, timeout=120)
        except (subprocess.SubprocessError, OSError) as e:
            detail = ""
            if isinstance(e, subprocess.CalledProcessError) and e.stderr:
                detail = ": " + e.stderr.decode(errors="replace")[-200:]
            _BUILD_ERROR = f"build failed ({type(e).__name__}{detail})"
            warnings.warn(
                f"anomod native build failed ({type(e).__name__}{detail}); "
                "falling back to stale .so or pure Python",
                RuntimeWarning, stacklevel=2)
    if not _SO_PATH.exists():
        if _BUILD_ERROR is None:
            _BUILD_ERROR = f"{_SO_PATH} missing and no build attempted " \
                           "(no Makefile or not stale)"
        return None
    try:
        lib = ctypes.CDLL(str(_SO_PATH))
    except OSError as e:
        _BUILD_ERROR = f"dlopen failed: {e}"
        return None
    try:
        _bind(lib)
    except AttributeError as e:
        # symbols missing (e.g. make failed against a stale .so): degrade to
        # the pure-Python fallbacks rather than raising from available()
        _BUILD_ERROR = f"stale .so missing symbols: {e}"
        return None
    _LIB = lib
    _BUILD_ERROR = None
    return _LIB


def _bind(lib: ctypes.CDLL) -> None:
    lib.anomod_scan_log_mt.restype = ctypes.c_int64
    lib.anomod_scan_log_mt.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int8), ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64, ctypes.c_int32]
    lib.anomod_scan_api_jsonl.restype = ctypes.c_int64
    lib.anomod_scan_api_jsonl.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int16), ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
    lib.anomod_rt_create.restype = ctypes.c_void_p
    lib.anomod_rt_create.argtypes = [ctypes.c_int32]
    lib.anomod_rt_destroy.restype = None
    lib.anomod_rt_destroy.argtypes = [ctypes.c_void_p]
    lib.anomod_rt_n_threads.restype = ctypes.c_int32
    lib.anomod_rt_n_threads.argtypes = [ctypes.c_void_p]
    lib.anomod_rt_summarize_logs.restype = ctypes.c_int64
    lib.anomod_rt_summarize_logs.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double)]
    lib.anomod_scan_csv_cols.restype = ctypes.c_int64
    lib.anomod_scan_csv_cols.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64]
    lib.anomod_stage_lanes.restype = ctypes.c_int64
    lib.anomod_stage_lanes.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_int64]
    lib.anomod_stage_lanes_mat.restype = ctypes.c_int64
    lib.anomod_stage_lanes_mat.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64]
    lib.anomod_sfq_drain.restype = ctypes.c_int64
    lib.anomod_sfq_drain.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int64, ctypes.c_double, ctypes.POINTER(ctypes.c_int64)]
    lib.anomod_sfq_victim.restype = ctypes.c_int64
    lib.anomod_sfq_victim.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int64]


def available() -> bool:
    return _load() is not None


def build_error() -> Optional[str]:
    """Why the native runtime is unusable (None while it is fine)."""
    _load()
    return _BUILD_ERROR


def mode() -> str:
    """The validated ANOMOD_NATIVE knob value: auto | on | off."""
    from anomod.config import get_config
    return get_config().native


def enabled() -> bool:
    """The ONE gate every native consumer dispatches through (the ingest
    scanners and the serve staging alike): honors the validated
    ``ANOMOD_NATIVE`` knob on top of :func:`available` — ``off`` forces
    the pure-Python paths, ``on`` REQUIRES the runtime (raising with the
    recorded build-failure reason rather than silently degrading), and
    ``auto`` (default) uses it iff it loads."""
    m = mode()
    if m == "off":
        return False
    ok = available()
    if m == "on" and not ok:
        raise RuntimeError(
            "ANOMOD_NATIVE=on but the native runtime is unusable: "
            f"{_BUILD_ERROR or 'unknown load failure'} — rebuild with "
            "`make -C native smoke` or unset ANOMOD_NATIVE to accept the "
            "pure-Python fallback")
    return ok


def staging_enabled(override: Optional[bool] = None) -> bool:
    """Resolve the serve staging path's native switch: an explicit
    ``override`` (a python-staging reference run passes False;
    True demands the runtime like ``ANOMOD_NATIVE=on``) beats the env
    knob; ``None`` defers to :func:`enabled`."""
    if override is None:
        return enabled()
    if not override:
        return False
    if not available():
        raise RuntimeError(
            "native staging requested but the runtime is unusable: "
            f"{_BUILD_ERROR or 'unknown load failure'}")
    return True


def sfq_kernels(require: bool = False):
    """The admission plane's columnar SFQ drain/shed kernels
    (``anomod_sfq_drain`` / ``anomod_sfq_victim``): the bound library
    handle, or None when the columnar engine should fall back to its
    pure-NumPy scans.

    ``require=True`` is the ``ANOMOD_SERVE_NATIVE_DRAIN=on`` contract —
    raise with the recorded build-failure reason instead of silently
    serving the fallback (the ``staging_enabled(override=True)``
    discipline); ``require=False`` defers to :func:`enabled`, so
    ``ANOMOD_NATIVE=off`` forces the NumPy scans like every other
    native consumer."""
    if require:
        if not available():
            raise RuntimeError(
                "ANOMOD_SERVE_NATIVE_DRAIN=on but the native runtime is "
                f"unusable: {_BUILD_ERROR or 'unknown load failure'} — "
                "rebuild with `make -C native` or set "
                "ANOMOD_SERVE_NATIVE_DRAIN=auto to accept the NumPy "
                "fallback")
        return _LIB
    return _LIB if enabled() else None


def status() -> dict:
    """The native runtime's health document (JSON-able): knob value,
    availability, .so path and the build-failure reason when unusable —
    surfaced by ``anomod validate`` and ``chip_smoke.py``."""
    ok = available()
    m = mode()
    out = {
        "mode": m,
        "available": ok,
        "so_path": str(_SO_PATH) if _SO_PATH.exists() else None,
        "build_error": _BUILD_ERROR,
        "staging": bool(ok and m != "off"),
    }
    if m == "on" and not ok:
        out["error"] = ("ANOMOD_NATIVE=on but the native runtime is "
                        "unusable — see build_error")
    return out


def scan_log(text: bytes, n_threads: int = 4) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(levels int8, timestamps float64) per line; None if native unavailable."""
    lib = _load()
    if lib is None:
        return None
    max_lines = text.count(b"\n") + 1
    levels = np.empty(max_lines, np.int8)
    ts = np.empty(max_lines, np.float64)
    n = lib.anomod_scan_log_mt(
        text, len(text),
        levels.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        ts.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        max_lines, n_threads)
    return levels[:n], ts[:n]


class Runtime:
    """Persistent native thread-pool executor (anomod_rt_* ABI).

    One pool serves many batch submissions; per-thread read buffers are
    reused across files.  Use as a context manager, or rely on
    :func:`default_runtime` for a process-wide singleton.
    """

    def __init__(self, n_threads: int = 4):
        lib = _load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._ptr = lib.anomod_rt_create(n_threads)

    @property
    def n_threads(self) -> int:
        return int(self._lib.anomod_rt_n_threads(self._ptr))

    def close(self) -> None:
        if self._ptr:
            self._lib.anomod_rt_destroy(self._ptr)
            self._ptr = None

    def __enter__(self) -> "Runtime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def summarize_logs(self, paths) -> Tuple[np.ndarray, np.ndarray, int]:
        """Parallel per-file log summary sweep.

        Returns ``(counts [N,5] int64, ts [N,2] float64, n_readable)`` where
        counts rows are {n_lines, n_info, n_warn, n_error, size_bytes} and
        ts rows are {min_ts, max_ts} (0 when absent).
        """
        enc = [str(p).encode() for p in paths]
        arr = (ctypes.c_char_p * len(enc))(*enc)
        counts = np.zeros((len(enc), 5), np.int64)
        ts = np.zeros((len(enc), 2), np.float64)
        n = self._lib.anomod_rt_summarize_logs(
            self._ptr, arr, len(enc),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        return counts, ts, int(n)


_DEFAULT_RT: Optional[Runtime] = None


def default_runtime() -> Optional[Runtime]:
    """Process-wide executor (4 workers), created lazily; None if no lib."""
    global _DEFAULT_RT
    if _DEFAULT_RT is None and _load() is not None:
        import atexit
        _DEFAULT_RT = Runtime(4)
        atexit.register(_DEFAULT_RT.close)
    return _DEFAULT_RT


def summarize_log_files(paths) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(counts [N,5], ts [N,2]) via the default runtime; None if native
    unavailable.  Unreadable files yield all-zero rows."""
    rt = default_runtime()
    if rt is None or not paths:
        return None
    counts, ts, _ = rt.summarize_logs(paths)
    return counts, ts


def scan_csv_columns(text: bytes, cols,
                     skip_header: bool = True) -> Optional[np.ndarray]:
    """Parse numeric CSV columns natively: [n_cols, n_rows] float64 with NaN
    for non-numeric fields.  None if native unavailable."""
    lib = _load()
    if lib is None:
        return None
    max_rows = text.count(b"\n") + 1
    cols_arr = np.asarray(list(cols), np.int32)
    out = np.empty((len(cols_arr), max_rows), np.float64)
    n = lib.anomod_scan_csv_cols(
        text, len(text),
        cols_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(cols_arr), int(skip_header),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), max_rows)
    return out[:, :n]


def aligned_empty(shape, dtype, align: int = 64) -> np.ndarray:
    """An uninitialized C-contiguous array whose data pointer is
    ``align``-byte aligned.  The serve scratch ring allocates through this
    so XLA:CPU's zero-copy host-buffer aliasing applies to the pinned
    ``[lanes, width]`` slots the executables read — ``np.empty`` only
    guarantees 16-byte alignment, and an unaligned buffer silently costs
    a copy per dispatch."""
    dt = np.dtype(dtype)
    shape = tuple(int(s) for s in np.atleast_1d(shape)) \
        if not np.isscalar(shape) else (int(shape),)
    size = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
    buf = np.empty(size + align, np.uint8)
    ofs = (-buf.ctypes.data) % align
    return buf[ofs:ofs + size].view(dt).reshape(shape)


class StagedChunk(dict):
    """One staged chunk's column views PLUS the matrix-carrier fields
    the native fast path reads: the chunk is ``mat[:, lo:lo+m]`` of a
    C-contiguous ``[n_cols, stride]`` float32 staging matrix
    (anomod.replay.stage_columns_fused), so a whole lane marshals as
    three ints — ``ptr`` (``mat`` data pointer + 4·lo, precomputed ONCE
    per staged batch, where a per-call ``.ctypes.data`` extraction costs
    as much as a small numpy copy on a slow host), ``stride`` (the
    matrix row length in elements) and ``m`` (live rows).  ``mat`` is
    held only to keep the pointer's backing memory alive.  Behaves as
    the plain column dict everywhere else — consumers that feed jit
    (pytree) must convert with ``dict(cols)``."""

    __slots__ = ("mat", "ptr", "stride", "m")


class StagePlan:
    """Per-scratch-slot marshalling cache for the GIL-free native pack.

    The pinned ``[lanes, width]`` scratch buffers live for the runner's
    lifetime, so everything about them — destination pointers, per-column
    fill patterns, dtype checks, the ctypes argument arrays — marshals
    ONCE here instead of per dispatch (the per-call ctypes setup is what
    made a naive wrapper slower than the interpreter fill it replaced).
    Per call only the live lanes' source descriptors are written:
    three ints per lane when the chunks are :class:`StagedChunk` matrix
    carriers (the serve path), or per-column pointer extraction as the
    general fallback for plain dicts.

    Built via :func:`make_stage_plan`; ``stage(group_cols)`` returns
    False (caller runs the interpreter fill) on any contract break —
    never stages garbage bytes.
    """

    __slots__ = ("_lib", "_rt_ptr", "_keys", "_dtypes", "_n_cols",
                 "_lanes", "_width", "_expect", "_dst", "_fills", "_rows",
                 "_bases", "_strides", "_src", "_mat_ok")

    def __init__(self, lib, scratch, fill_for, mat_keys=None):
        keys = list(scratch)
        first = scratch[keys[0]]
        if first.ndim != 2:
            raise ValueError("scratch buffers must be [lanes, width]")
        lanes, width = map(int, first.shape)
        n_cols = len(keys)
        self._dst = (ctypes.c_void_p * n_cols)()
        self._fills = (ctypes.c_uint32 * n_cols)()
        dtypes = []
        for c, k in enumerate(keys):
            buf = scratch[k]
            if (buf.shape != (lanes, width) or buf.dtype.itemsize != 4
                    or not buf.flags.c_contiguous):
                raise ValueError(f"scratch[{k!r}] breaks the 4-byte "
                                 "C-contiguous [lanes, width] contract")
            self._dst[c] = buf.ctypes.data
            self._fills[c] = int(np.array([fill_for(k)],
                                          dtype=buf.dtype).view(np.uint32)[0])
            dtypes.append(buf.dtype)
        self._lib = lib
        rt = default_runtime()
        self._rt_ptr = rt._ptr if rt is not None else None
        self._keys = keys
        self._dtypes = dtypes
        self._n_cols = n_cols
        self._lanes = lanes
        self._width = width
        self._expect = n_cols * lanes * width
        self._rows = (ctypes.c_int64 * lanes)()
        self._bases = (ctypes.c_void_p * lanes)()
        self._strides = (ctypes.c_int64 * lanes)()
        self._src = None                     # lazily, general path only
        #: matrix fast path is sound only when the scratch columns are
        #: exactly the staged matrix's rows, in row order
        self._mat_ok = (mat_keys is not None
                        and keys == list(mat_keys))

    def stage(self, group_cols) -> bool:
        """Pack ``group_cols`` (one unpadded chunk per live lane) into
        the planned scratch slot, dead-filling row tails and dead lanes
        — byte-identical to the interpreter fill, GIL released for the
        whole native call."""
        n_live = len(group_cols)
        if n_live > self._lanes:
            return False
        if self._mat_ok:
            try:
                rows, bases, strides = self._rows, self._bases, \
                    self._strides
                width = self._width
                for i, cols in enumerate(group_cols):
                    m = cols.m
                    if m > width or cols.mat.shape[0] != self._n_cols:
                        return False
                    rows[i] = m
                    bases[i] = cols.ptr
                    strides[i] = cols.stride
            except AttributeError:
                pass                         # plain dicts: general path
            else:
                n = self._lib.anomod_stage_lanes_mat(
                    self._rt_ptr, self._dst, bases, strides, rows,
                    self._fills, self._n_cols, n_live, self._lanes,
                    self._width)
                return n == self._expect
        return self._stage_ptrs(group_cols, n_live)

    def _stage_ptrs(self, group_cols, n_live: int) -> bool:
        """The general path: per-column pointer extraction from plain
        column dicts (arbitrary 1-D 4-byte arrays), with the full
        dtype/contiguity contract checked per column."""
        if self._src is None:
            self._src = (ctypes.c_void_p * (self._n_cols * self._lanes))()
        src, rows, width = self._src, self._rows, self._width
        k0 = self._keys[0]
        for i, cols in enumerate(group_cols):
            m = cols[k0].shape[0]
            if m > width:
                return False
            rows[i] = m
        for c, k in enumerate(self._keys):
            want = self._dtypes[c]
            base = c * n_live
            for i, cols in enumerate(group_cols):
                col = cols[k]
                if (col.dtype != want or col.ndim != 1
                        or col.shape[0] != rows[i]
                        or not col.flags.c_contiguous):
                    return False
                src[base + i] = col.ctypes.data
        n = self._lib.anomod_stage_lanes(
            self._rt_ptr, self._dst, src, rows, self._fills,
            self._n_cols, n_live, self._lanes, self._width)
        return n == self._expect


def make_stage_plan(scratch, fill_for,
                    mat_keys=None) -> Optional[StagePlan]:
    """A :class:`StagePlan` for the pinned ``scratch`` slot, or None when
    the native runtime is unavailable or the slot breaks the 4-byte
    C-contiguous contract (caller keeps the interpreter fill).
    ``mat_keys`` (the staged-matrix row order, anomod.replay.STAGE_KEYS)
    enables the matrix fast path when the scratch keys match it."""
    lib = _load()
    if lib is None or not scratch:
        return None
    try:
        return StagePlan(lib, scratch, fill_for, mat_keys=mat_keys)
    except ValueError:
        return None


def stage_lanes(scratch, group_cols, fill_for) -> bool:
    """Pack one fused dispatch's lane scratch NATIVELY, GIL-free.

    ``scratch`` maps column name -> the pinned ``[lanes, width]`` buffer,
    ``group_cols`` is the ordered list of live lanes' unpadded column
    dicts, ``fill_for(key)`` the per-column dead-row fill scalar.  The
    result is byte-identical to the interpreter fill
    (``buf[i, :m] = col; buf[i, m:] = fill; buf[n_live:] = fill`` per
    column) — every chunk column is a 4-byte dtype, so the native copy is
    dtype-blind memcpy + pattern fill.  Returns False (caller falls back
    to the Python fill) when the runtime is unavailable or any array
    breaks the 4-byte / C-contiguous / dtype-match contract.

    The ctypes call releases the GIL for its whole duration, and large
    slots fan the per-column fills across the persistent native thread
    pool (:func:`default_runtime`) — staging for scratch slot k+1 can
    make progress under the in-flight dispatch on slot k, and shard
    workers stage concurrently instead of convoying on the interpreter
    lock (the GIL-overlap smoke in tests/test_native.py pins this).

    One-shot convenience over :func:`make_stage_plan` — the serve hot
    loop caches a :class:`StagePlan` per pinned slot instead, so the
    per-call marshalling cost here (pointer extraction per column) is
    paid once per slot, not per dispatch.
    """
    plan = make_stage_plan(scratch, fill_for)
    return plan is not None and plan.stage(group_cols)


def scan_api_jsonl(text: bytes) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """(status int16, latency_ms float32, content_length int32) per record."""
    lib = _load()
    if lib is None:
        return None
    max_recs = text.count(b"\n") + 1
    status = np.empty(max_recs, np.int16)
    lat = np.empty(max_recs, np.float32)
    clen = np.empty(max_recs, np.int32)
    n = lib.anomod_scan_api_jsonl(
        text, len(text),
        status.ctypes.data_as(ctypes.POINTER(ctypes.c_int16)),
        lat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        clen.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        max_recs)
    return status[:n], lat[:n], clen[:n]
