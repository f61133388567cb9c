"""Sanitizer lane driver (ISSUE 14): build + run the native core under
TSan/ASan/UBSan, with zero-report as the enforceable contract.

Two lanes, both seeded and reproducible:

- **Native stress lane** (:func:`run_stress`): ``sanstress.cpp`` is
  compiled TOGETHER with ``core.cpp`` into a standalone executable,
  entirely under one sanitizer — no Python in the process, so every
  reported frame is our code and zero-report needs no suppressions.
  Scenarios drive insert/steal/cancel/abort/obs-ring-drain/concurrent-
  scrape schedules (the PR 13 ``pdtd_stats``-vs-ring-growth race is a
  pinned scenario); the ``PARSEC_SAN_YIELD`` injection points compiled
  into the variant widen the interleaving space per seed.
- **Python lane** (:func:`run_python_lane`): a fresh interpreter with
  ``PARSEC_NATIVE_SAN=<variant>`` and the gcc sanitizer runtime
  LD_PRELOADed runs a real workload on the sanitized ``.so`` — this is
  the "reproducible via ``native.sanitize=tsan``" surface an operator
  uses against a suspicious serving binary.

Skips are CLEAN and explicit: :func:`capable` probes the toolchain
once per variant (compile + link + run of a trivial program) so CI on
a container without sanitizer runtimes skips instead of failing.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import threading
from typing import Dict, Optional, Tuple

from . import SAN_FLAGS, build_flags, sanitizer_runtime

_HERE = os.path.dirname(os.path.abspath(__file__))
_CORE = os.path.join(_HERE, "core.cpp")
_STRESS = os.path.join(_HERE, "sanstress.cpp")
_BUILD_DIR = os.path.join(_HERE, "build")

#: markers a sanitizer prints per finding — the zero-report scan
REPORT_MARKERS = (
    "WARNING: ThreadSanitizer",
    "ERROR: AddressSanitizer",
    "ERROR: LeakSanitizer",
    "runtime error:",               # UBSan
    "SUMMARY: UndefinedBehaviorSanitizer",
)

#: every stress scenario the driver knows (sanstress.cpp main)
SCENARIOS = ("pdtd", "plifo", "phash", "pmempool", "pgraph")

_lock = threading.Lock()
_capable: Dict[str, Optional[str]] = {}     # variant -> None | reason


def sanitizer_env(var: str, preload: bool = True) -> Dict[str, str]:
    """Environment for running variant ``var``: report-to-exit-code
    options plus (``preload=True``, the Python lane) the LD_PRELOAD of
    the gcc runtime. ``detect_leaks=0`` for ASan under CPython — the
    interpreter intentionally leaks at exit and those frames are
    third-party by definition (the native stress lane runs WITH leak
    detection, where every frame is ours)."""
    env = {
        "TSAN_OPTIONS": "exitcode=66 " +
                        os.environ.get("TSAN_OPTIONS", ""),
        "UBSAN_OPTIONS": "print_stacktrace=1 " +
                         os.environ.get("UBSAN_OPTIONS", ""),
    }
    if preload:
        env["ASAN_OPTIONS"] = ("detect_leaks=0 exitcode=66 " +
                               os.environ.get("ASAN_OPTIONS", ""))
        rt = sanitizer_runtime(var)
        if rt:
            prior = os.environ.get("LD_PRELOAD", "")
            env["LD_PRELOAD"] = rt + (":" + prior if prior else "")
    else:
        env["ASAN_OPTIONS"] = ("exitcode=66 " +
                               os.environ.get("ASAN_OPTIONS", ""))
    return env


def capable(var: str) -> Optional[str]:
    """None when variant ``var`` can compile, link AND run in this
    container; otherwise the human-readable reason to skip."""
    if var not in SAN_FLAGS:
        return f"unknown variant {var!r}"
    with _lock:
        if var in _capable:
            return _capable[var]
    import tempfile
    reason: Optional[str] = None
    with tempfile.TemporaryDirectory(prefix="parsec_san_") as td:
        src = os.path.join(td, "probe.cpp")
        exe = os.path.join(td, "probe")
        with open(src, "w") as f:
            f.write("#include <thread>\n"
                    "int main(){int x=0;std::thread t([&]{x=1;});"
                    "t.join();return x-1;}\n")
        try:
            proc = subprocess.run(
                ["g++", *build_flags(var), "-pthread", "-o", exe, src],
                capture_output=True, text=True, timeout=120)
            if proc.returncode != 0:
                reason = (f"{var} probe compile failed: "
                          f"{proc.stderr[-200:]}")
            else:
                run = subprocess.run(
                    [exe], capture_output=True, text=True, timeout=60,
                    env={**os.environ, **sanitizer_env(var,
                                                       preload=False)})
                if run.returncode != 0:
                    reason = (f"{var} probe run failed rc="
                              f"{run.returncode}: {run.stderr[-200:]}")
        except FileNotFoundError:
            reason = "g++ not found on PATH"
        except (OSError, subprocess.SubprocessError) as exc:
            reason = f"{var} probe errored: {exc}"
    with _lock:
        _capable[var] = reason
    return reason


def count_reports(text: str) -> int:
    return sum(text.count(m) for m in REPORT_MARKERS)


def _stress_stamp(var: str) -> str:
    h = hashlib.sha256()
    for p in (_CORE, _STRESS):
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(" ".join(build_flags(var)).encode())
    return h.hexdigest()[:16]


def build_stress(var: str) -> str:
    """Compile the stress driver for variant ``var`` (cached under
    ``_native/build/`` keyed by source hashes + flags). Raises
    RuntimeError with the compiler tail on failure."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    exe = os.path.join(_BUILD_DIR, f"sanstress_{var}")
    stamp = exe + ".stamp"
    want = _stress_stamp(var)
    if os.path.exists(exe):
        try:
            with open(stamp) as f:
                if f.read().strip() == want:
                    return exe
        except OSError:
            pass
    cmd = ["g++", *build_flags(var), "-Wall", "-Wextra", "-Werror",
           "-pthread", "-o", exe + ".tmp", _CORE, _STRESS]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=300)
    if proc.returncode != 0:
        raise RuntimeError(f"sanstress {var} build failed: "
                           f"{proc.stderr[-500:]}")
    os.replace(exe + ".tmp", exe)
    with open(stamp, "w") as f:
        f.write(want)
    return exe


def run_stress(var: str, scenario: str = "all", seed: int = 42,
               iters: int = 2, timeout: int = 300) -> dict:
    """One stress run; returns {rc, reports, output} — the zero-report
    contract is ``rc == 0 and reports == 0``."""
    exe = build_stress(var)
    env = {**os.environ, **sanitizer_env(var, preload=False)}
    proc = subprocess.run([exe, scenario, str(seed), str(iters)],
                          capture_output=True, text=True,
                          timeout=timeout, env=env)
    out = (proc.stdout or "") + (proc.stderr or "")
    return {"rc": proc.returncode, "reports": count_reports(out),
            "output": out[-4000:]}


def py_lane_script(var: str, n_tasks: int = 400,
                   marker: str = "SANLANE_OK") -> str:
    """The canonical Python-lane workload: a real DTD pool on the
    sanitized variant, asserting the sanitized engine actually engaged
    (variant selected, yield points compiled in, native pool live)
    before printing ``marker``."""
    return f'''
import parsec_tpu as parsec
from parsec_tpu import _native
from parsec_tpu.data import LocalCollection
from parsec_tpu.dsl import dtd

assert _native.variant() == {var!r}, _native.variant()
assert _native.available(), _native.build_error()
assert _native.load().psan_yield_enabled() == 1   # sanitized variant
ctx = parsec.init(nb_cores=4)
ctx.start()
C = LocalCollection("C", {{(i,): 0 for i in range(8)}})
tp = dtd.Taskpool("sanlane")
ctx.add_taskpool(tp)
def bump(x):
    return x + 1
tp.insert_tasks(bump, [(dtd.TileArg(C, (i % 8,), dtd.INOUT),)
                       for i in range({n_tasks})])
assert tp._native is not None, "sanitized engine must engage"
tp.wait()
assert sum(C.data_of((i,)) for i in range(8)) == {n_tasks}
parsec.fini(ctx)
print({marker!r})
'''


def run_python_lane(var: str, script: str,
                    timeout: int = 600) -> Tuple[int, str]:
    """Run ``script`` in a fresh interpreter on the sanitized variant:
    ``PARSEC_NATIVE_SAN=<var>`` selects the build, the sanitizer
    runtime rides LD_PRELOAD. Returns (rc, combined output). The repo
    root is prepended to PYTHONPATH so the subprocess imports THIS
    checkout."""
    from . import _build
    # build the variant HERE (no preload in this process): the lane
    # subprocess must only dlopen — compiling under LD_PRELOAD would
    # run the compiler itself through the sanitizer
    _build(var)
    repo = os.path.dirname(os.path.dirname(_HERE))
    env = {**os.environ, **sanitizer_env(var, preload=True)}
    env["PARSEC_NATIVE_SAN"] = var
    env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True,
                          timeout=timeout, env=env)
    return proc.returncode, (proc.stdout or "") + (proc.stderr or "")


def clang_tidy_available() -> bool:
    import shutil
    return shutil.which("clang-tidy") is not None


def run_clang_tidy(checks: str = "concurrency-*,bugprone-*") -> dict:
    """clang-tidy's concurrency/bugprone checks over core.cpp (the
    tier-1 gate runs this only when the binary exists)."""
    proc = subprocess.run(
        ["clang-tidy", f"-checks=-*,{checks}", _CORE, "--",
         "-std=c++17", "-pthread"],
        capture_output=True, text=True, timeout=600)
    out = (proc.stdout or "") + (proc.stderr or "")
    return {"rc": proc.returncode,
            "warnings": out.count(" warning: "),
            "output": out[-4000:]}
