"""Compile-once serving: persistent executor cache + shared jit store.

PaRSEC's core compile economy is that a *task class* is compiled once
(JDF -> parsec_ptgpp at build time) and instances are nearly free; our
compiled executors re-lowered per (N, taskpool) and paid a multi-second
XLA stall on every new problem size — the PARITY compile-time-scaling
table shows 20-70 s warm for the panel-fused flagship, minutes for
whole-DAG jit at NT=32. This module restores the once-per-program
economics with three layers:

1. **In-process shared jit store** (:func:`cached_jit`): jitted
   callables keyed by a *semantic* key — body code fingerprints, tile
   geometry, bucket shape, trace-affecting MCA knobs — instead of by
   function object. Rebuilding an executor (or a whole Context) for an
   already-served bucket never re-traces (``jax.jit`` caches by function
   identity, so every fresh wrapper used to pay a full re-trace).
2. **Persistent executor store** (:class:`ExecutorStore`): AOT
   ``lower() -> compile() -> serialize_executable`` keyed by a
   :func:`lowering_fingerprint` covering a digest of the package's
   source (:func:`package_digest`), jax/jaxlib versions, device
   kind/count, and the caller's key parts (NB, dtype, bucket shape,
   body hooks, mesh/sharding). A cache hit
   skips tracing AND lowering AND XLA — the second *process* to serve a
   bucket pays only deserialization. (The XLA persistent cache, by
   contrast, must re-trace and re-lower the whole program just to
   compute its key — that IS the 20-70 s "warm" cost.)
3. The classic **XLA persistent compilation cache** toggle
   (:func:`enable_compile_cache`), kept as the safety net for programs
   that bypass the store.

Env/knob interaction (documented contract, ONE resolution —
:func:`_resolve_dir` — shared by chip_smoke.py, ``benchmark/run.py``
and the examples):

- ``JAX_COMPILATION_CACHE_DIR`` env: the cache placed from OUTSIDE
  (setting it is also the opt-in). Where it is set, the XLA cache stays
  exactly where JAX itself put it (this module never rewrites
  ``jax_compilation_cache_dir`` then), the executor store lives under
  ``<that dir>/executors``, and no knob, ``PARSEC_COMPILE_CACHE`` path
  or explicit argument moves either.
- ``jit.cache_dir`` MCA knob (env ``PARSEC_MCA_jit_cache_dir``):
  ``""`` = disabled (library default), ``auto`` = the FIXED
  ``.xla_cache`` next to the repo root (the path is part of the XLA
  cache key, so a directory that moves never hits), anything else =
  that directory. chip_smoke.py, ``benchmark/run.py`` and the
  compiled-path examples set it to ``auto`` — entry points opt in; the
  library never writes caches unasked.
- ``PARSEC_COMPILE_CACHE`` env: kill switch. ``0`` disables BOTH layers
  whatever else is set; a path overrides the knob's directory.
  :func:`enable_compile_cache` remains the explicit call.
- ``jit.cache_salt`` MCA knob: extra fingerprint salt — flip it to
  force a cold cache without deleting files (tests use this for the
  version-salt invalidation contract).

Cache layout under ``<dir>/``: XLA's own cache files at the top level
(unchanged), serialized executables under ``executors/<digest>.pkl``
(pickle of {schema, key, payload, in_tree, out_tree}; the digest is the
sha256 lowering fingerprint, so key checks are pure file existence).
The store is a local trust domain (pickle), like the XLA cache itself.
"""

from __future__ import annotations

import functools
import hashlib
import os
import pickle
import threading
import types
from typing import Any, Callable, Dict, Optional, Tuple

from . import mca_param
from .debug import debug_verbose, warning

_SCHEMA = 2      # 2: records carry the compiled-for device ids

mca_param.register(
    "jit.cache_dir", "",
    help="persistent compile-cache directory for the compiled executors "
         "('' = disabled, 'auto' = .xla_cache next to the repo root, "
         "else a path). Enables BOTH the XLA persistent cache and the "
         "serialized-executor store; PARSEC_COMPILE_CACHE=0 is the "
         "kill switch that overrides this knob")
mca_param.register(
    "jit.cache_salt", "",
    help="extra salt mixed into every lowering fingerprint; flip to "
         "invalidate the executor store without deleting files")
mca_param.register(
    "jit.persist_executors", 1,
    help="serialize AOT-compiled executables into the cache dir "
         "(0 = in-process jit sharing only)")


# ---------------------------------------------------------------------------
# trace-affecting MCA knobs
# ---------------------------------------------------------------------------
# Compiled bodies and wave fusers read MCA parameters at TRACE time
# (potrf.trsm_hook picks the TRSM kernel, ops.matmul_precision the MXU
# pass count, ...). Two traces of the same function under different
# knob values produce different programs, so every shared-cache key
# must include the resolved values — components register the knobs
# whose values their traced code depends on, and the fingerprint
# snapshots all of them. Over-invalidation (a knob flip missing caches
# that never read it) is accepted: correctness over hit rate.

_TRACE_KNOBS: set = set()
_TK_LOCK = threading.Lock()


def register_trace_knob(name: str) -> None:
    """Declare ``name`` as an MCA param whose value affects traced
    programs; its resolved value enters every lowering fingerprint."""
    with _TK_LOCK:
        _TRACE_KNOBS.add(name)


def trace_knob_snapshot() -> Tuple[Tuple[str, Any], ...]:
    with _TK_LOCK:
        names = sorted(_TRACE_KNOBS)
    return tuple((n, mca_param.get(n)) for n in names)


# ---------------------------------------------------------------------------
# compilation counters (jax.monitoring)
# ---------------------------------------------------------------------------
# '/jax/core/compile/backend_compile_duration' fires once per actual
# XLA backend compile (persistent-cache hits do NOT fire it) — the
# counter the compile-once tests assert on instead of wall clock.

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counters = {"backend_compiles": 0, "store_hits": 0, "store_misses": 0,
             "store_errors": 0, "jit_store_hits": 0}
_listener_installed = False
_CNT_LOCK = threading.Lock()


def _install_listener() -> None:
    global _listener_installed
    with _CNT_LOCK:
        if _listener_installed:
            return
        _listener_installed = True
    from jax import monitoring

    def _on_duration(event, duration, **kwargs):  # noqa: ARG001
        if event == _BACKEND_COMPILE_EVENT:
            with _CNT_LOCK:
                _counters["backend_compiles"] += 1

    monitoring.register_event_duration_secs_listener(_on_duration)


def backend_compile_count() -> int:
    """Process-wide count of actual XLA backend compiles since the
    counter was first consulted (monitoring listener installed lazily —
    call once BEFORE the region you want counted)."""
    _install_listener()
    with _CNT_LOCK:
        return _counters["backend_compiles"]


def cache_stats() -> Dict[str, int]:
    with _CNT_LOCK:
        return dict(_counters)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def function_fingerprint(fn: Callable) -> Tuple[bool, str]:
    """``(stable, digest)`` for a Python callable's *traced behavior*:
    code objects (recursively through nested consts and closure
    functions), module/qualname, default args, and closure-cell
    literals. ``stable=True`` means the digest is reproducible across
    processes (safe to persist / share across equal rebuilds);
    ``stable=False`` means some ingredient (an unhashable closure cell,
    a bound method of a stateful object) fell back to ``id()`` — valid
    only per-process AND only while the caller keeps the object alive,
    so unstable fingerprints must stay in per-instance caches.

    Deliberately NOT covered: the code of *global* functions the body
    calls by name (only the name is hashed) — the package's are covered
    by its source digest in :func:`lowering_fingerprint`, a body's own
    file outside the package by :func:`source_digest`, and
    runtime-variant behavior must go through registered trace knobs."""
    import numpy as np

    parts = []
    stable = [True]
    seen = set()

    def code(c: types.CodeType) -> None:
        parts.append(hashlib.sha256(c.co_code).hexdigest()[:16])
        parts.append(str(c.co_names))
        parts.append(str(c.co_varnames))
        for const in c.co_consts:
            if isinstance(const, types.CodeType):
                code(const)
            else:
                lit(const, 0)

    def lit(v: Any, depth: int) -> None:
        if depth > 8:
            stable[0] = False
            parts.append("depth")
            return
        if v is None or isinstance(v, (bool, int, float, str, bytes,
                                       complex)):
            parts.append(repr(v))
        elif isinstance(v, (tuple, frozenset)):
            parts.append("(")
            for x in (sorted(v, key=repr) if isinstance(v, frozenset)
                      else v):
                lit(x, depth + 1)
            parts.append(")")
        elif isinstance(v, np.dtype):
            parts.append(str(v))
        elif isinstance(v, types.FunctionType):
            walk(v, depth + 1)
        elif isinstance(v, types.CodeType):
            code(v)
        elif isinstance(v, types.ModuleType):
            # a module in a closure cell (``import jax.numpy as jnp`` in
            # the enclosing function): name-identified, as the globals a
            # body calls by name are
            parts.append(f"module:{v.__name__}")
        else:
            stable[0] = False
            parts.append(f"id:{id(v)}")

    def walk(f: Callable, depth: int) -> None:
        if id(f) in seen:       # cycles through closure cells
            parts.append("cycle")
            return
        seen.add(id(f))
        if isinstance(f, functools.partial):
            parts.append("partial")
            walk(f.func, depth + 1)
            for a in f.args:
                lit(a, depth + 1)
            for k in sorted(f.keywords or {}):
                parts.append(k)
                lit(f.keywords[k], depth + 1)
            return
        c = getattr(f, "__code__", None)
        if c is None:
            # builtins / callable objects: name-identified only
            parts.append(getattr(f, "__module__", "") or "")
            qn = getattr(f, "__qualname__", None)
            if qn is None:
                stable[0] = False
                parts.append(f"obj:{id(f)}")
            else:
                parts.append(qn)
            return
        parts.append(getattr(f, "__module__", "") or "")
        parts.append(getattr(f, "__qualname__", c.co_name))
        code(c)
        for cell in getattr(f, "__closure__", None) or ():
            try:
                lit(cell.cell_contents, depth + 1)
            except ValueError:       # empty cell
                parts.append("emptycell")
        for d in getattr(f, "__defaults__", None) or ():
            lit(d, depth + 1)

    walk(fn, 0)
    digest = hashlib.sha256("\x00".join(parts).encode()).hexdigest()
    return stable[0], digest


def _canon(part: Any, out: list) -> None:
    """Canonicalize one key part into hashable strings (handles
    ShapeDtypeStructs, dtypes, arrays-as-shapes, nested containers)."""
    import numpy as np
    if part is None or isinstance(part, (bool, int, float, str, bytes,
                                         complex)):
        out.append(repr(part))
    elif isinstance(part, (tuple, list)):
        out.append("(")
        for p in part:
            _canon(p, out)
        out.append(")")
    elif isinstance(part, dict):
        out.append("{")
        for k in sorted(part, key=repr):
            out.append(repr(k))
            _canon(part[k], out)
        out.append("}")
    elif isinstance(part, np.dtype):
        out.append(str(part))
    elif hasattr(part, "shape") and hasattr(part, "dtype"):
        out.append(f"sds{tuple(part.shape)}:{np.dtype(part.dtype)}")
    elif isinstance(part, types.FunctionType):
        out.append(function_fingerprint(part)[1])
    else:
        out.append(repr(part))


def _device_signature() -> Tuple:
    import jax
    devs = jax.devices()
    d = devs[0]
    return (d.platform, getattr(d, "device_kind", "?"), len(devs))


# the package's root, and the suffixes of the sources it is built from:
# its Python modules and the native engine g++ builds from them
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SOURCES = (".py", ".cpp", ".cc", ".h")


@functools.lru_cache(maxsize=None)
def _tree_digest(root: str) -> str:
    """sha256 over every source file under ``root``, by its path under
    ``root`` and its bytes: a copy of the tree elsewhere digests the
    same, one byte changed anywhere digests otherwise."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in ("__pycache__", "build"))
        for name in sorted(filenames):
            if name.endswith(_SOURCES):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode() + b"\x00")
                with open(path, "rb") as fh:
                    h.update(hashlib.sha256(fh.read()).digest())
    return h.hexdigest()


def package_digest() -> str:
    """The digest of the package's source, computed once a process: a
    program the store keeps is the program of this code, whatever the
    body calls by name (:func:`function_fingerprint`) or a lowering reads
    from a module constant."""
    return _tree_digest(_PACKAGE)


@functools.lru_cache(maxsize=None)
def _file_digest(path: str) -> str:
    with open(path, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()


@functools.lru_cache(maxsize=None)
def _installed_roots() -> Tuple[str, ...]:
    import sysconfig
    paths = sysconfig.get_paths()
    return tuple(os.path.abspath(paths[k]) + os.sep
                 for k in ("stdlib", "platstdlib", "purelib", "platlib")
                 if k in paths)


def source_digest(fn: Callable) -> str:
    """Digest of the files that define ``fn`` where they lie outside the
    package and outside the installation's libraries (a test's or a
    user's module): ``fn`` itself, through partials, and the functions
    the package's own wrappers close over (a DTD body's woven function
    holds the user's). ``""`` where every one is the package's, whose
    code :func:`package_digest` covers."""
    files, seen = set(), set()
    installed = _installed_roots()
    todo = [fn]
    while todo:
        f = todo.pop()
        if id(f) in seen:
            continue
        seen.add(id(f))
        if isinstance(f, functools.partial):
            todo.append(f.func)
            continue
        code = getattr(f, "__code__", None)
        path = os.path.abspath(getattr(code, "co_filename", "") or "")
        if path.startswith(_PACKAGE + os.sep):
            # a wrapper of the package's: look at what it closes over
            for cell in getattr(f, "__closure__", None) or ():
                try:
                    todo.append(cell.cell_contents)
                except ValueError:          # empty cell
                    pass
        elif os.path.isfile(path) and not path.startswith(installed):
            files.add(path)
    return "".join(_file_digest(p) for p in sorted(files))


def lowering_fingerprint(*key_parts: Any) -> str:
    """sha256 digest over the standard fingerprint fields + the
    caller's key parts. Standard fields: parsec_tpu version and the
    digest of its source (+ ``jit.cache_salt``), jax/jaxlib versions,
    backend device kind/count, and the registered trace-knob
    snapshot."""
    import jax
    import jaxlib
    from ..version import __version__
    out: list = [f"schema{_SCHEMA}", __version__, package_digest(),
                 str(mca_param.get("jit.cache_salt", "")),
                 jax.__version__, jaxlib.__version__,
                 repr(_device_signature())]
    _canon(trace_knob_snapshot(), out)
    for part in key_parts:
        _canon(part, out)
    return hashlib.sha256("\x00".join(out).encode()).hexdigest()


# ---------------------------------------------------------------------------
# persistent executor store
# ---------------------------------------------------------------------------

def _initialize_ffi_runtime() -> None:
    """Bind the CPU custom-call runtime before any deserialization.

    jaxlib's LAPACK custom-call stubs resolve their BLAS/LAPACK
    function pointers via ``_lapack.initialize()``, which jax invokes
    lazily from the LOWERING helpers. A warm serving process that only
    *deserializes* executables never lowers anything, so a loaded
    program containing a cholesky/triangular-solve custom call would
    dispatch through unbound pointers — measured as a hard segfault on
    the first such executable. Best-effort by design: absent modules
    (TPU-only jaxlib builds, future renames) just skip."""
    try:
        from jaxlib.cpu import _lapack
        _lapack.initialize()
    except Exception:  # noqa: BLE001 — registration is best-effort
        pass


class ExecutorStore:
    """Serialized-executable store: ``<root>/<digest>.pkl`` holding the
    AOT-compiled program and the ids of the devices it was compiled
    for. Writes are atomic (tmp + rename). A missing entry is a miss;
    an entry that exists but cannot be loaded RAISES — version skew and
    foreign devices change the digest and so can only miss, which
    leaves a failing load meaning a broken store, and a store that
    quietly recompiles hides exactly that."""

    def __init__(self, root: str) -> None:
        self.root = root
        os.makedirs(root, exist_ok=True)
        _initialize_ffi_runtime()

    def _path(self, digest: str) -> str:
        return os.path.join(self.root, digest + ".pkl")

    def load(self, digest: str) -> Optional[Callable]:
        path = self._path(digest)
        if not os.path.exists(path):
            with _CNT_LOCK:
                _counters["store_misses"] += 1
            return None
        import jax
        from jax.experimental import serialize_executable as se
        try:
            with open(path, "rb") as fh:
                rec = pickle.load(fh)
            # load onto the devices the program was COMPILED for:
            # deserialize_and_load defaults to every device of the
            # backend, which turns a one-device program on a
            # many-device host into one that rejects its own arguments
            by_id = {d.id: d for d in jax.devices()}
            fn = se.deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=[by_id[i] for i in rec["device_ids"]])
            # ...and check the backend honoured that: libtpu reloads a
            # one-device program compiled for a non-default chip onto
            # its FIRST chip, which would fail at the first call with
            # an error about buffers, far from here
            got = [d.id for d in fn.runtime_executable().local_devices()]
            if got != rec["device_ids"]:
                raise ValueError(
                    f"compiled for devices {rec['device_ids']} but the "
                    f"backend reloaded it onto {got}; compile it for the "
                    "default device, or with persist=False")
        except Exception as exc:
            with _CNT_LOCK:
                _counters["store_errors"] += 1
            raise RuntimeError(
                f"executor store entry {path} exists but cannot be "
                f"loaded ({type(exc).__name__}: {exc}); delete it or "
                "change jit.cache_salt") from exc
        with _CNT_LOCK:
            _counters["store_hits"] += 1
        debug_verbose(3, "jitcache", "store hit %s (%s)",
                      digest[:12], rec.get("key", "?")[:80])
        return fn

    def save(self, digest: str, compiled: Any, key_repr: str) -> None:
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            rec = {"key": key_repr, "payload": payload,
                   "in_tree": in_tree, "out_tree": out_tree,
                   "device_ids": [
                       d.id for d in
                       compiled.runtime_executable().local_devices()]}
            tmp = self._path(digest) + f".tmp{os.getpid()}"
            with open(tmp, "wb") as fh:
                pickle.dump(rec, fh)
            os.replace(tmp, self._path(digest))
        except Exception as exc:  # noqa: BLE001 — cache is best-effort
            warning("jitcache", "store save %s failed: %s",
                    digest[:12], exc)


_store: Optional[ExecutorStore] = None
_store_checked = False
_store_gen = -1        # mca generation the negative check was made at
_STORE_LOCK = threading.Lock()


def _default_dir() -> str:
    return os.path.join(os.path.dirname(_PACKAGE), ".xla_cache")


def _resolve_dir(path: Optional[str] = None) -> Optional[str]:
    """The ONE directory resolution (explicit call, knob auto-enable,
    every entry point): PARSEC_COMPILE_CACHE=0 kills everything;
    JAX_COMPILATION_CACHE_DIR, where set, is where the cache was placed
    from outside and nothing below moves it; then explicit path >
    PARSEC_COMPILE_CACHE path > jit.cache_dir knob ('auto' -> the fixed
    repo .xla_cache)."""
    env = os.environ.get("PARSEC_COMPILE_CACHE", "")
    if env == "0":
        return None
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if placed:
        return placed
    if path is not None:
        return path
    if env:
        return env
    knob = str(mca_param.get("jit.cache_dir", "")).strip()
    if knob in ("", "0", "off"):
        return None
    return _default_dir() if knob == "auto" else knob


def enable_compile_cache(path: str | None = None) -> str | None:
    """Turn on JAX's persistent compilation cache AND the serialized-
    executor store at the directory :func:`_resolve_dir` names
    (``path`` absent and nothing else set: ``.xla_cache`` next to the
    repo root). Safe to call repeatedly; returns the cache dir in use
    (None when disabled by ``PARSEC_COMPILE_CACHE=0``)."""
    global _store, _store_checked
    if os.environ.get("PARSEC_COMPILE_CACHE", "") == "0":
        with _STORE_LOCK:
            _store, _store_checked = None, True
        return None
    path = _resolve_dir(path) or _default_dir()
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # placed from outside, JAX already reads it from the env
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    with _STORE_LOCK:
        if _store is None or _store.root != os.path.join(path, "executors"):
            _store = ExecutorStore(os.path.join(path, "executors"))
        _store_checked = True
    return path


def disable_compile_cache() -> None:
    """Drop the executor store (tests; the XLA cache dir config is left
    as-is — it is process state the caller owns)."""
    global _store, _store_checked
    with _STORE_LOCK:
        _store, _store_checked = None, False


def executor_store() -> Optional[ExecutorStore]:
    """The active store, auto-enabling from the ``jit.cache_dir`` knob
    on first use (the knob path — bench/examples — needs no manual
    :func:`enable_compile_cache` call). A negative answer is re-checked
    whenever the MCA registry changes, so setting the knob after a
    disabled lookup still enables the store."""
    global _store_checked, _store_gen
    gen = mca_param.generation()
    with _STORE_LOCK:
        if _store is not None or (_store_checked and _store_gen == gen):
            return _store
    d = _resolve_dir()
    if d is None:
        with _STORE_LOCK:
            _store_checked = True
            _store_gen = gen
        return None
    enable_compile_cache(d)
    return _store


# ---------------------------------------------------------------------------
# shared jit store
# ---------------------------------------------------------------------------

_JIT_STORE: Dict[str, Callable] = {}
_JIT_LOCK = threading.Lock()


def reset_in_process_cache() -> None:
    """Drop the in-process shared jit store (tests simulate a fresh
    process to exercise the persistent layer)."""
    with _JIT_LOCK:
        _JIT_STORE.clear()


def jit_store_size() -> int:
    with _JIT_LOCK:
        return len(_JIT_STORE)


def persistent_store() -> Optional[ExecutorStore]:
    """The store a program with ``persist=True`` goes to: the active one
    (:func:`executor_store`) unless ``jit.persist_executors`` is 0."""
    if not int(mca_param.get("jit.persist_executors", 1)):
        return None
    return executor_store()


def cached_jit(fn: Callable, **kwargs) -> Callable:
    """The compiled path's jit entry point: a callable shared in-process
    by semantic ``key`` and (when the store is enabled and
    ``example_args`` abstract shapes are given) AOT-compiled +
    serialized under the :func:`lowering_fingerprint` of that key.

    - in-process hit: the existing callable, zero tracing.
    - store hit: deserialize, zero tracing/lowering/XLA.
    - miss with ``example_args``: ``jit(fn).lower(*args).compile()``
      EAGERLY (so warm-up passes like ``prepare_segments`` really
      resolve every compile up front), serialized for the next process
      when the store is enabled. The returned executable accepts
      exactly the example shapes — callers put every shape in the key.
    - miss without ``example_args``: a plain shared ``jax.jit`` wrapper
      (multi-shape; in-process sharing only).

    ``jit_wrapper`` overrides ``jax.jit`` construction (the pjit front
    end passes shardings through it). Keys MUST cover everything that
    changes the trace: the caller's code fingerprints, shapes/dtypes,
    bucket sizes — the standard fields (versions, source, device, trace
    knobs, salt) are added by :func:`lowering_fingerprint`. Keywords
    as :func:`cached_program`'s.
    """
    return cached_program(fn, **kwargs)[0]


def cached_program(fn: Callable, *, key: Tuple, example_args: Tuple = None,
                   donate_argnums=(), static_argnums=(),
                   jit_wrapper: Callable = None,
                   persist: bool = True) -> Tuple[Callable, str]:
    """:func:`cached_jit`'s callable and where it came from:
    ``"process"`` (the in-process store: nothing traced now),
    ``"store"`` (deserialized: nothing traced or lowered) or
    ``"traced"`` (traced and lowered in this process, AOT here or, for a
    plain ``jax.jit`` wrapper, at its first call)."""
    digest = lowering_fingerprint(*key)
    with _JIT_LOCK:
        hit = _JIT_STORE.get(digest)
    if hit is not None:
        with _CNT_LOCK:
            _counters["jit_store_hits"] += 1
        return hit, "process"
    import jax
    if jit_wrapper is not None:
        jitted = jit_wrapper(fn)
    else:
        jitted = jax.jit(fn, donate_argnums=donate_argnums,
                         static_argnums=static_argnums)
    result, origin = jitted, "traced"
    store = persistent_store() if persist else None
    if example_args is not None:
        loaded = store.load(digest) if store is not None else None
        if loaded is not None:
            result, origin = loaded, "store"
        else:
            # a failing AOT compile propagates: a plain-jit fallback
            # would only fail again at first call, minus the context
            result = jitted.lower(*example_args).compile()
            if store is not None:
                out: list = []
                _canon(key, out)
                store.save(digest, result, "|".join(out))
    with _JIT_LOCK:
        return _JIT_STORE.setdefault(digest, result), origin
