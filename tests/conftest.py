"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip hardware is not available in CI; sharding/SPMD tests run on a
virtual 8-device CPU mesh (the same validation the driver's
dryrun_multichip performs). Must run before jax is first imported.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The suite runs on the CPU platform (the tier-1 command also says
# JAX_PLATFORMS=cpu); the chip is reached only through chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
# tests place their caches in tmp dirs; a cache placed from outside for
# real runs must not swallow them
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import shutil

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# optional-tool matrix (ISSUE 19 satellite): the tier-1 suite skips a
# handful of tests when an external binary is missing.  Detect each tool
# ONCE here and make the skips loud — the reason and the install hint
# appear in the pytest header and the end-of-run summary instead of
# hiding inside `-rs` output.  The matrix is documented in README.md
# ("Static verification" -> optional tools).
# ---------------------------------------------------------------------------

_OPTIONAL_TOOLS = {
    # tool -> (what skips without it, install hint)
    "clang-tidy": ("tests/test_native_san.py clang-tidy concurrency "
                   "gate (1 test)",
                   "apt-get install clang-tidy"),
    "ruff": ("tests/test_analysis_cli.py + tests/test_native_san.py "
             "python-lint gates (2 tests)",
             "pip install ruff"),
    "g++": ("tests/test_native_san.py -Werror compile gate and every "
            "native-engine lane",
            "apt-get install g++"),
}

_missing_tools = [t for t in _OPTIONAL_TOOLS if shutil.which(t) is None]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-bound protocheck sweeps and other long lanes — "
        "deselected in tier-1 (-m 'not slow')")


def pytest_report_header(config):
    if not _missing_tools:
        return ["optional tools: all present "
                f"({', '.join(sorted(_OPTIONAL_TOOLS))})"]
    return [f"optional tool missing: {t} — skips {_OPTIONAL_TOOLS[t][0]};"
            f" install: {_OPTIONAL_TOOLS[t][1]}"
            for t in _missing_tools]


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _missing_tools:
        return
    tr = terminalreporter
    tr.ensure_newline()
    tr.section("optional tools not installed", sep="-", yellow=True)
    for t in _missing_tools:
        what, hint = _OPTIONAL_TOOLS[t]
        tr.line(f"{t}: skipped {what} — install with `{hint}` to run "
                "the full matrix")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def ctx():
    """A small runtime context, torn down after the test."""
    import parsec_tpu as parsec
    c = parsec.init(nb_cores=4)
    c.start()
    yield c
    parsec.fini(c)


def spd_matrix(rng, n, dtype=np.float32):
    """Random symmetric positive-definite matrix."""
    M = rng.standard_normal((n, n)).astype(np.float64)
    A = M @ M.T + n * np.eye(n)
    return A.astype(dtype)
