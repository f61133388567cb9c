"""chip_smoke.py's contract, as far as a machine without a chip can
check it: no chip and no stated dry run -> non-zero exit naming the
missing device and no result line; the stated CPU dry run passes every
phase quickly; a failing phase makes the exit code non-zero."""

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _run(args, cache_dir, code=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    cmd = [sys.executable, "-c", code] if code else [sys.executable, _SMOKE]
    return subprocess.run(cmd + list(args), capture_output=True, text=True,
                          timeout=600, env=env, cwd=_ROOT)


def test_no_chip_fails_and_names_the_device(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout          # no result line at all


def test_cpu_dry_run_passes_every_phase(tmp_path):
    t0 = time.perf_counter()
    proc = _run(["--dry-run-cpu"], tmp_path)
    elapsed = time.perf_counter() - t0
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert "platform=cpu" in lines[0]
    verdicts = [ln for ln in lines if "verdict=" in ln]
    assert len(verdicts) == 9 and all("verdict=PASS" in v for v in verdicts)
    assert "kernel=interpreted" in proc.stdout
    assert elapsed < 60, elapsed
    # the cache went where it was placed from outside, nowhere else
    assert f"cache_dir={tmp_path}" in lines[0]
    assert os.listdir(tmp_path / "executors")


def test_failing_phase_gives_nonzero_exit(tmp_path):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "def boom(sizes, on_chip): raise RuntimeError('made to fail')\n"
        "chip_smoke.ONE_CHIP[0] = ('store', boom)\n"
        "sys.exit(chip_smoke.main(sys.argv[1:]))\n" % _ROOT)
    proc = _run(["--dry-run-cpu", "--phases", "store,flash"], tmp_path,
                code=code)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["failed"] == ["store"]
    assert "made to fail" in proc.stdout
    assert "[flash] verdict=PASS" in proc.stdout     # the rest still ran
