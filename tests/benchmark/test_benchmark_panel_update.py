"""``panel_update_share`` (PR 40): the share of the flagship's device time
in the products of its UPDATE waves, read off the scope the one-chip
panel fuser opens around them. That ``BENCHMARK.json`` declares it for
``potrf_panel_n40960`` alone and that it resolves to the scope reader;
that the reader finds the scope's operations in a device plane as the
program names them and reads nothing from a program without the scope (a
tree before PR 40); that the program the cell's driver compiles opens the
scope; and the cell's traced rehearsal, which on a CPU has no device
plane: the line then leaves the metric out and the run is none the worse.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, main  # noqa: E402
from tests.benchmark.test_benchmark_getrf_incpiv import _xspace  # noqa: E402

MAN = Manifest(ROOT)
NAME, CELL, SCOPE = "panel_update_share", "potrf_panel_n40960", \
    "parsec:panel_update"
SCOPES = MAN.reader("device_seconds_by_scope")


def test_it_is_declared_for_the_flagship_alone_and_resolves():
    (entry,) = [m for m in MAN.bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "step_s_p50", "workloads": [CELL]}
    assert NAME in {m["name"] for m in MAN.metrics_for("per_layer", CELL)}
    assert "step_s_p50" in {m["name"]
                            for m in MAN.metrics_for("end_to_end", CELL)}
    for other in MAN.bench["workloads"]:
        if other["name"] != CELL:
            assert NAME not in {m["name"] for m in MAN.metrics_for(
                "per_layer", other["name"])}
    spec = MAN.metric(NAME)
    assert spec["reader"] == "device_seconds_by_scope" and spec["what"]
    assert spec["params"] == {"scope": SCOPE}
    assert callable(SCOPES.read)


def test_the_reader_finds_the_products_and_a_parent_reads_nothing(
        tmp_path, monkeypatch):
    s = 10 ** 12                                    # a second, in ps
    change = [(f"jit(run_state)/{SCOPE}/dot_general:", 10 * s, s),
              ("jit(run_state)/dot_general:", 11 * s, s // 4),
              (f"jit(run_state)/{SCOPE}/dot_general:", 12 * s, s // 2),
              ("jit(run_state)/cholesky:", 13 * s, s // 4)]
    bench = [("traced", 0.0, 100.0), ("step", 9.0, 14.0)]
    for name, ops_, seconds in (
            ("change", change, 1.5),
            # the parent's program: the same products, fused with their
            # subtraction and under no scope
            ("parent", [(stack.replace(f"/{SCOPE}", ""), t0, dt)
                        for stack, t0, dt in change], None)):
        path = tmp_path / f"{name}.xplane.pb"
        path.write_bytes(_xspace(ops_))
        found = SCOPES.scope_intervals(str(path), SCOPE)
        got = SCOPES.seconds_in_steps(found, bench)
        assert got == (pytest.approx(seconds) if seconds else None)
    # and without a trace at all
    monkeypatch.setattr(SCOPES, "_CHECKOUT", str(tmp_path))
    record = {"cell": CELL, "trace": {}, "setup": {}, "peaks": None}
    assert SCOPES.read(record, MAN.metric(NAME)["params"]) is None


def test_the_cells_program_opens_the_scope_around_its_products_alone():
    """The program the cell's driver compiles (``build_potrf_left`` on
    ``PanelExecutor``, the configuration's knobs), at rehearsal size:
    every operation under the scope is a product's, and the subtractions
    and the solves lie outside it."""
    import jax
    from parsec_tpu.algorithms.potrf import build_potrf_left
    from parsec_tpu.compiled.panels import PanelExecutor
    from parsec_tpu.compiled.wavefront import plan_taskpool
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.utils import mca_param

    config = MAN.config(MAN.cell(CELL)["config"])
    assert config["taskpool"].endswith(":build_potrf_left")
    for knob, value in config["knobs"].items():
        mca_param.set(knob, value)
    try:
        ex = PanelExecutor(plan_taskpool(build_potrf_left(
            TiledMatrix(256, 256, 64, 64, name="A"))))
        text = jax.jit(ex.run_state).lower(ex.state_shapes()).as_text(
            debug_info=True)
    finally:
        for knob in config["knobs"]:
            mca_param.unset(knob)
    # the name stacks the lowering gives its operations, as the trace
    # will: "jit(run_state)/parsec:panel_update/dot_general"
    under = {stack.rsplit("/", 1)[1] for stack in re.findall(
        r'loc\("(jit\(run_state\)/[^"]*)"', text)
        if SCOPE in stack.split("/")}
    assert under == {"slice", "transpose", "dot_general"}
    assert ex.lowering_report()["update_runs"] == 3   # a run a step here


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of this module's own: other test files rehearse the same
    cell at the same time, and a cell's trace has one place per tree."""
    import shutil
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(root)


def test_the_traced_rehearsal_is_none_the_worse_without_a_device_plane(
        capsys, checkout):
    rc = main(["--workload", CELL, "--seed", "4000000019", "--seconds",
               "0.5", "--trace", "1", "--dry-run-cpu=1"], root=checkout)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    # a CPU's trace has no device plane: nothing to read, nothing printed
    assert NAME + DRY_SUFFIX not in last["metrics"]
    assert "compiles_in_window" + DRY_SUFFIX in last["metrics"]
