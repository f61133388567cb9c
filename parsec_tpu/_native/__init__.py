"""Native C++ core loader.

Builds ``core.cpp`` into a shared library with g++ on first use (cached
next to the source, keyed by a hash of the source — an edited core.cpp
rebuilds instead of silently loading the stale binary) and exposes it
through ctypes. The Python runtime falls back to its pure-Python
implementations when the toolchain is unavailable (``load() -> None``),
so the package works everywhere; ``build_error()`` reports WHY the
library is missing so callers that require it (``runtime.native_dtd=1``)
can fail loudly instead of silently degrading. On a real deployment the
native engine carries the dependency-tracking, dynamic-task (DTD), and
static-DAG execution hot paths, mirroring the reference where those
layers are native C (parsec/parsec.c, parsec/scheduling.c,
parsec/interfaces/dtd/insert_function.c, parsec/class/*).

Sanitizer build lane (ISSUE 14): ``native.sanitize = off|tsan|asan|
ubsan`` (MCA knob; env ``PARSEC_NATIVE_SAN`` wins so sanitized
subprocesses need no MCA plumbing) selects a BUILD VARIANT. Each
variant compiles to its own cached binary (``libparsec_core.tsan.so``,
…) whose stamp records the source hash AND the flag set, so sanitized
and production binaries coexist and neither can be served stale for
the other. Sanitizer variants compile with ``-DPARSEC_SAN_YIELD=1``
(the seeded yield-injection points that widen the explored
interleaving space) at ``-O1 -g``; the production variant is exactly
the PR 10 build. Loading a sanitized variant into a Python process
requires the sanitizer runtime to be preloaded (``LD_PRELOAD`` of
:func:`sanitizer_runtime`'s path) — ``_native/sanlane.py`` wraps that
subprocess dance and the all-native stress driver
(``sanstress.cpp``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "core.cpp")
_SO = os.path.join(_HERE, "libparsec_core.so")
_STAMP = _SO + ".srchash"

#: sanitizer variants: variant -> the g++ flags that define it. The
#: production variant ("off") is the plain -O2 build; every sanitizer
#: variant compiles the PSAN_YIELD injection points in.
SAN_FLAGS = {
    "tsan": ("-fsanitize=thread",),
    "asan": ("-fsanitize=address", "-fno-omit-frame-pointer"),
    "ubsan": ("-fsanitize=undefined", "-fno-sanitize-recover=undefined"),
}
#: gcc runtime library each variant's .so needs preloaded when loaded
#: into an unsanitized host process (CPython)
SAN_RUNTIME_LIB = {"tsan": "libtsan.so", "asan": "libasan.so",
                   "ubsan": "libubsan.so"}
#: pdtd lock-discipline recorder domains, in C enum order (core.cpp
#: PdtdLockDomain) — index = domain id inside the pdtd_stats
#: ``lock_pairs`` bitmask (bit held*5+acquired)
PDTD_LOCK_DOMAINS = ("entry", "grow", "overflow", "cv", "ring")

try:                                    # MCA knob for the lane; the env
    from ..utils import mca_param as _mca   # var PARSEC_NATIVE_SAN wins
    _mca.register(
        "native.sanitize", "off",
        choices=("off", "tsan", "asan", "ubsan"),
        help="native-core build variant: off (production -O2) | "
             "tsan/asan/ubsan (sanitizer-instrumented, cached "
             "per-variant; env PARSEC_NATIVE_SAN overrides)")
except Exception:  # pragma: no cover — direct import outside the pkg
    _mca = None

_lock = threading.Lock()
_libs: Dict[str, Optional[ctypes.CDLL]] = {}
_tried_variants: set = set()
_build_errors: Dict[str, str] = {}

BODY_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_uint32, ctypes.c_int32)

#: pdtd_stats slot names, in the C ABI's out[20] order. The obs_* rows
#: are the native observability plane (ISSUE 13): records written to /
#: dropped from the per-worker event rings, plus the current ring depth
#: (a gauge — excluded from the context's retired-pool folding, like
#: inflight/ready).
PDTD_STAT_KEYS = (
    "inserted", "linked_deps", "ready_pushed", "popped", "stolen",
    "overflow_pushed", "completed_native", "completed_python",
    "released_edges", "output_drops", "dropped_cancelled",
    "ring_highwater", "inflight", "ready", "pump_calls",
    "obs_recorded", "obs_dropped", "obs_ring_depth",
    # lock-discipline recorder (ISSUE 14): lock_pairs is the
    # (held*5+acquired) acquisition-pair BITMASK over
    # PDTD_LOCK_DOMAINS — OR-folded across engines, never summed;
    # lock_acquires counts recorded acquisitions (0 unless
    # pdtd_lockdbg_enable was called)
    "lock_pairs", "lock_acquires")

#: numpy dtype mirroring the C PdtdObsRec (48-byte fixed stride): one
#: binary record per completed native-engine task, expanded to the
#: PR 9 trace-record format at scrape time (profiling/trace.py)
OBS_REC_FIELDS = [("t0_ns", "<u8"), ("t1_ns", "<u8"), ("q_ns", "<u8"),
                  ("span", "<u8"), ("seq", "<u4"), ("parent_seq", "<u4"),
                  ("cls", "<u4"), ("worker", "<i4")]
OBS_PARENT_NONE = 0xFFFFFFFF


def obs_dtype():
    import numpy as np
    dt = np.dtype(OBS_REC_FIELDS)
    assert dt.itemsize == 48, dt.itemsize   # must match the C struct
    return dt


def _src_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def variant() -> str:
    """The ACTIVE build variant: env ``PARSEC_NATIVE_SAN`` first (so a
    sanitized subprocess lane needs only one env var), then the
    ``native.sanitize`` MCA knob. Unknown values raise — a typo'd
    sanitizer name must not silently mean "production build"."""
    v = os.environ.get("PARSEC_NATIVE_SAN", "").strip().lower()
    if not v and _mca is not None:
        v = str(_mca.get("native.sanitize", "off")).strip().lower()
    if v in ("", "0", "off", "none", "false"):
        return "off"
    if v not in SAN_FLAGS:
        raise ValueError(
            f"unknown native sanitizer variant {v!r}; choices are "
            f"off, {', '.join(sorted(SAN_FLAGS))}")
    return v


def so_path(var: str = "off") -> str:
    """Per-variant binary path: sanitized and production .so coexist."""
    return _SO if var == "off" else \
        os.path.join(_HERE, f"libparsec_core.{var}.so")


def build_flags(var: str = "off"):
    """The g++ flag set defining variant ``var`` (part of its cache
    stamp — a flag change rebuilds)."""
    if var == "off":
        return ["-O2", "-std=c++17"]
    return ["-O1", "-g", "-DPARSEC_SAN_YIELD=1", *SAN_FLAGS[var],
            "-std=c++17"]


def _stamp_want(var: str) -> str:
    # production stamp stays the bare source hash (the PR 10 format, so
    # an existing deployment's stamp remains valid); variant stamps add
    # the flag set
    h = _src_hash()
    return h if var == "off" else h + " " + " ".join(build_flags(var))


def sanitizer_runtime(var: str) -> Optional[str]:
    """Absolute path of the gcc sanitizer runtime to LD_PRELOAD when
    loading variant ``var``'s .so into an unsanitized process, or None
    when unresolvable (no g++ / static-only runtime)."""
    name = SAN_RUNTIME_LIB.get(var)
    if name is None:
        return None
    try:
        out = subprocess.run(["g++", f"-print-file-name={name}"],
                             capture_output=True, text=True, timeout=30)
        path = out.stdout.strip()
        if path and path != name and os.path.exists(path):
            return os.path.abspath(path)
    except (OSError, subprocess.SubprocessError):
        pass
    return None


def _stamped(so: str, want: str) -> bool:
    """``so`` is there and its stamp says it was built from ``want``."""
    if not os.path.exists(so):
        return False
    try:
        with open(so + ".srchash") as f:
            return f.read().strip() == want
    except OSError:
        return False                # pre-hash .so (or stamp lost): rebuild


def _build(var: str = "off") -> bool:
    so = so_path(var)
    try:
        want = _stamp_want(var)
    except OSError as exc:
        _build_errors[var] = f"cannot read {_SRC}: {exc}"
        return False
    if _stamped(so, want):
        return True
    # a name of this process's own: processes that start together (pytest
    # -n 6) each compile, and each renames a whole file into place
    tmp = so + f".{os.getpid()}.tmp"
    cmd = ["g++", *build_flags(var), "-shared", "-fPIC", "-pthread",
           "-o", tmp, _SRC]
    # never compile UNDER a sanitizer runtime: a sanitized Python lane
    # (LD_PRELOAD=libtsan) would otherwise run g++/cc1plus themselves
    # through TSan's shadow — observed as a multi-minute hang
    env = dict(os.environ)
    env.pop("LD_PRELOAD", None)
    try:
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=240, env=env)
        except FileNotFoundError:
            _build_errors[var] = "g++ not found on PATH"
            return False
        if not _stamped(so, want):      # else another process was first
            os.replace(tmp, so)
            with open(tmp, "w") as f:
                f.write(want)
            os.replace(tmp, so + ".srchash")
        return True
    except subprocess.CalledProcessError as exc:
        tail = (exc.stderr or b"").decode(errors="replace")[-500:]
        _build_errors[var] = f"g++ failed (rc={exc.returncode}): {tail}"
    except (OSError, subprocess.SubprocessError) as exc:
        _build_errors[var] = f"build failed: {exc}"
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # this build failed; another process may have got there first. A
    # binary whose stamp does not match the source is never loaded: what
    # runs is what this checkout's core.cpp builds, or nothing
    return _stamped(so, want)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64, u32, i32, p = (ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int32,
                        ctypes.c_void_p)
    lib.pdep_new.restype = p
    lib.pdep_free.argtypes = [p]
    lib.pdep_size.argtypes = [p]
    lib.pdep_size.restype = u64
    lib.pdep_update.argtypes = [p, u64, u64, u32, ctypes.c_int, i32,
                                ctypes.POINTER(i32)]
    lib.pdep_update.restype = ctypes.c_int
    lib.pdep_finalize.argtypes = [p, u64, u64, ctypes.c_int,
                                  ctypes.POINTER(i32)]
    lib.pdep_finalize.restype = ctypes.c_int
    lib.plevel_kahn.argtypes = [u64, u64, ctypes.POINTER(u32),
                                ctypes.POINTER(u32), ctypes.POINTER(i32)]
    lib.plevel_kahn.restype = ctypes.c_int
    lib.pgraph_new.argtypes = [u32, ctypes.POINTER(i32), ctypes.POINTER(i32),
                               u64, ctypes.POINTER(u32), ctypes.POINTER(u32),
                               BODY_FN, ctypes.c_int]
    lib.pgraph_new.restype = p
    lib.pgraph_free.argtypes = [p]
    lib.pgraph_run.argtypes = [p]
    lib.pgraph_run.restype = ctypes.c_int
    lib.pgraph_remaining.argtypes = [p]
    lib.pgraph_remaining.restype = u32
    lib.pgraph_consume.argtypes = [p, u32]
    lib.pgraph_consume.restype = ctypes.c_int
    # pdtd: dynamic-task engine (DTD insert→release hot loop)
    lib.pdtd_new.argtypes = [ctypes.c_int, u32]
    lib.pdtd_new.restype = p
    lib.pdtd_free.argtypes = [p]
    lib.pdtd_insert.argtypes = [p, u32, ctypes.POINTER(i32),
                                ctypes.POINTER(ctypes.c_uint8),
                                ctypes.POINTER(u32), ctypes.POINTER(u32),
                                ctypes.POINTER(ctypes.c_uint8), u32]
    lib.pdtd_insert.restype = ctypes.c_int64
    lib.pdtd_arm.argtypes = [p, u32, u32]
    lib.pdtd_pump.argtypes = [p, ctypes.c_int, ctypes.POINTER(u32)]
    lib.pdtd_pump.restype = ctypes.c_int
    lib.pdtd_pump_batch.argtypes = [p, ctypes.c_int, ctypes.POINTER(u32),
                                    ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    lib.pdtd_pump_batch.restype = ctypes.c_int
    lib.pdtd_complete.argtypes = [p, ctypes.c_int, u32,
                                  ctypes.POINTER(u32), i32,
                                  ctypes.POINTER(i32), u64, u64]
    lib.pdtd_complete.restype = ctypes.c_int
    lib.pdtd_complete_batch.argtypes = [p, ctypes.c_int,
                                        ctypes.POINTER(u32), ctypes.c_int,
                                        ctypes.POINTER(u64)]
    lib.pdtd_complete_batch.restype = ctypes.c_int
    lib.pdtd_inflight.argtypes = [p]
    lib.pdtd_inflight.restype = u32
    lib.pdtd_ready.argtypes = [p]
    lib.pdtd_ready.restype = u32
    lib.pdtd_wait_below.argtypes = [p, u32, ctypes.c_int]
    lib.pdtd_wait_below.restype = u32
    lib.pdtd_cancel.argtypes = [p]
    lib.pdtd_stats.argtypes = [p, ctypes.POINTER(u64)]
    # sanitizer lane + lock-discipline recorder (ISSUE 14)
    lib.psan_seed.argtypes = [u64]
    lib.psan_yield_enabled.restype = ctypes.c_int
    lib.pdtd_lockdbg_enable.argtypes = [p]
    # pdtd observability plane (ISSUE 13): per-worker event rings
    lib.pdtd_obs_now.argtypes = []
    lib.pdtd_obs_now.restype = u64
    lib.pdtd_obs_enable.argtypes = [p, u64, u32]
    lib.pdtd_obs_enable.restype = ctypes.c_int
    lib.pdtd_obs_disable.argtypes = [p]
    lib.pdtd_obs_drain.argtypes = [p, ctypes.c_int, p, u32]
    lib.pdtd_obs_drain.restype = ctypes.c_int
    # foundation classes (reference parsec/class/*)
    lib.plifo_new.argtypes = [u32]
    lib.plifo_new.restype = p
    lib.plifo_free.argtypes = [p]
    lib.plifo_push.argtypes = [p, u64]
    lib.plifo_push.restype = ctypes.c_int
    lib.plifo_pop.argtypes = [p, ctypes.POINTER(u64)]
    lib.plifo_pop.restype = ctypes.c_int
    lib.plifo_size.argtypes = [p]
    lib.plifo_size.restype = u32
    lib.phash_new.argtypes = [u32]
    lib.phash_new.restype = p
    lib.phash_free.argtypes = [p]
    lib.phash_insert.argtypes = [p, u64, u64]
    lib.phash_insert.restype = ctypes.c_int
    lib.phash_find.argtypes = [p, u64, ctypes.POINTER(u64)]
    lib.phash_find.restype = ctypes.c_int
    lib.phash_remove.argtypes = [p, u64, ctypes.POINTER(u64)]
    lib.phash_remove.restype = ctypes.c_int
    lib.phash_size.argtypes = [p]
    lib.phash_size.restype = u64
    lib.pmempool_new.argtypes = [u32, ctypes.c_int]
    lib.pmempool_new.restype = p
    lib.pmempool_free.argtypes = [p]
    lib.pmempool_alloc.argtypes = [p, ctypes.c_int]
    lib.pmempool_alloc.restype = p
    lib.pmempool_release.argtypes = [p, ctypes.c_int, p]
    lib.pmempool_outstanding.argtypes = [p]
    lib.pmempool_outstanding.restype = u64
    lib.pmempool_allocated.argtypes = [p]
    lib.pmempool_allocated.restype = u64
    return lib


def load(var: Optional[str] = None) -> Optional[ctypes.CDLL]:
    """The native library for build variant ``var`` (default: the
    ACTIVE variant — ``native.sanitize`` / ``PARSEC_NATIVE_SAN``), or
    None when it cannot be built/loaded. Loading a sanitizer variant
    requires its runtime preloaded into the process (sanlane.py runs
    that in a subprocess); a bare dlopen without it fails here and the
    error names the runtime."""
    try:
        v = variant() if var is None else var
    except ValueError:
        # build_error() re-derives the message from variant() itself
        return None
    with _lock:
        if v in _tried_variants:
            return _libs.get(v)
        _tried_variants.add(v)
        _libs[v] = None
        lib = None
        if os.environ.get("PARSEC_NO_NATIVE"):
            _build_errors[v] = "disabled by PARSEC_NO_NATIVE"
        elif not _build(v):
            _build_errors.setdefault(v, "build failed")
        else:
            so = so_path(v)
            try:
                lib = _bind(ctypes.CDLL(so))
            except OSError as exc:
                hint = ""
                if v != "off":
                    rt = sanitizer_runtime(v)
                    hint = (f" (sanitized variant: LD_PRELOAD="
                            f"{rt or SAN_RUNTIME_LIB[v]} is required)")
                _build_errors[v] = f"dlopen({so}) failed: {exc}{hint}"
            except AttributeError as exc:
                # a stale .so missing newly-added symbols: the
                # source-hash stamp normally prevents this; surface it
                # instead of a confusing partial bind
                _build_errors[v] = f"stale {so}: {exc}"
        _libs[v] = lib
        return lib


def available() -> bool:
    return load() is not None


def build_error() -> Optional[str]:
    """Why the native library is unavailable (None when it loaded, or
    when load() was never attempted)."""
    if load() is not None:
        return None
    try:
        v = variant()
    except ValueError as exc:
        return str(exc)
    return _build_errors.get(v) or "native library unavailable"


def kahn_levels(n: int, edges) -> "Optional[list]":
    """Batch-level a DAG natively; edges = iterable of (src, dst).
    Returns per-task levels, or None if native is unavailable.
    Raises RuntimeError on a cycle."""
    import numpy as np
    lib = load()
    if lib is None:
        return None
    src = np.fromiter((e[0] for e in edges), dtype=np.uint32,
                      count=len(edges))
    dst = np.fromiter((e[1] for e in edges), dtype=np.uint32,
                      count=len(edges))
    out = np.zeros(n, dtype=np.int32)
    rc = lib.plevel_kahn(
        n, len(edges),
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc == -1:
        raise RuntimeError("DAG has a cycle")
    if rc != 0:
        raise RuntimeError(f"plevel_kahn failed: {rc}")
    return out.tolist()
