"""The ``dgetrf_1d_ptg_host`` configuration and its cell: that what
``BENCHMARK.json`` gained for them is declared and resolves (found by
name), the plain reference against ``numpy`` and LAPACK at test size, the
operation counts against a count by hand, the driver's check (a tile
rounded to bfloat16, a panel pivoted within its first tile only, an
unapplied SWPBACK, an invalid pivot, another step's matrix and a broken
storage guarantee each fail it), and the rehearsals, which print every new
metric a CPU can read under its ``_cpu_dryrun`` name. The cell's other
CPU dry runs come through ``test_benchmark_dryrun.py``'s parametrisation."""

import json
import os
import sys

import numpy as np
import pytest
import scipy.linalg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate, ops_getrf, ops_getrf_1d  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, Spans, main  # noqa: E402

MAN = Manifest(ROOT)
CELL, CONFIG = "getrf_1d_ptg_host_n32768_nb2048", "dgetrf_1d_ptg_host"
REF = MAN.reference("dgetrf_1d_ptg_host_reference")
# name -> (unit, source, moves, reader)
NEW = {
    "lu1d_host_us_per_task": ("us", "host_clock", "step_s_p50",
                              "host_us_per_task"),
    "lu1d_tasks_per_launch": ("ratio", "program_span", "step_s_p50",
                              "tasks_per_launch"),
    "lu1d_tasks_on_chip_share": ("%", "program_counter", "tflops_per_chip",
                                 "tasks_on_chip_share"),
    "lu1d_ranged_tiles_per_task": ("ratio", "program_counter", "step_s_p50",
                                   "counter_per_task"),
    "lu1d_device_step_s": ("s", "device_trace", "step_s_p50",
                           "device_step_s"),
    "lu1d_tile_roofline": ("%", "device_trace", "tflops_per_chip",
                           "roofline"),
    "lu1d_getrf_roofline": ("%", "device_trace", "tflops_per_chip",
                            "device_seconds_by_program"),
    "lu1d_swptrsm_roofline": ("%", "device_trace", "tflops_per_chip",
                              "device_seconds_by_program"),
    "lu1d_gemm_roofline": ("%", "device_trace", "tflops_per_chip",
                           "device_seconds_by_program"),
    "lu1d_panel_kernel_share": ("%", "device_trace", "step_s_p50",
                                "device_seconds_by_program"),
    "lu1d_swap_share": ("%", "device_trace", "step_s_p50",
                        "device_seconds_by_scope"),
}
DEVICE_TRACE = {n for n, spec in NEW.items() if spec[1] == "device_trace"}


def _named(section, name):
    (entry,) = [e for e in MAN.bench[section] if e["name"] == name]
    return entry


# -- what BENCHMARK.json gained, and that it resolves ------------------------

def test_the_configuration_and_the_cell_are_declared():
    config = _named("configs", CONFIG)
    assert config["file"] == "benchmark/configs/dgetrf_1d_ptg_host.json"
    assert config["reduced"] == [] and 0 < len(config["why"]) <= 200
    source = config["source"]
    assert "ICLDisco/dplasma" in source and "zgetrf_1d.jdf" in source
    assert "testing_zgetrf_1d.c" in source and len(source) <= 200
    assert "-N <n> -t <NB>" in source
    assert source == MAN.config(CONFIG)["source"]
    # no other configuration's source or file
    assert [c["name"] for c in MAN.bench["configs"]
            if c["source"] == source or c["file"] == config["file"]] == \
        [CONFIG]
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MAN.bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert MAN.bench["workloads"][-1] is cell       # appended, not spliced
    assert {"tflops_per_chip", "step_s_p50", "peak_hbm_gib", "setup_s"} <= {
        m["name"] for m in MAN.metrics_for("end_to_end", CELL)}
    # a quarter of ten cells is still two four-chip cells
    assert sum(w["chips"] == 4 for w in MAN.bench["workloads"]) == 2
    assert len(MAN.bench["workloads"]) == 10
    assert len(MAN.bench["configs"]) == 8


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_declared_for_the_cell_and_resolves(name):
    unit, source, moves, reader = NEW[name]
    entry = _named("per_layer", name)
    assert (entry["unit"], entry["source"], entry["moves"]) == \
        (unit, source, moves)
    assert entry["workloads"] == [CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] in {m["layer"] for m in MAN.bench["per_layer"]
                              if not m["name"].startswith("lu1d_")}
    assert name in {m["name"] for m in MAN.metrics_for("per_layer", CELL)}
    spec = MAN.metric(name)
    assert spec["name"] == name and spec["what"] and spec["reader"] == reader
    assert callable(MAN.reader(reader).read)
    if name.endswith("_roofline"):
        assert unit == "%"


def test_the_metrics_without_a_list_read_in_the_cell_too():
    names = {m["name"] for m in MAN.metrics_for("per_layer", CELL)}
    assert {"plan_compile_s", "compiles_in_window",
            "device_idle_share"} <= names
    assert len([n for n in names if n.startswith("lu1d_")]) == 11


def _driver(sizes, seed=5):
    import jax
    config = MAN.config(CONFIG)
    return MAN.driver(config["driver"]).build(
        config, {**config["sizes"], **sizes}, seed, jax.devices()[:1],
        Spans(), REF)


def test_the_configuration_file_states_the_deployment():
    config = MAN.config(CONFIG)
    workload = MAN.workload(CELL)
    assert workload["dry"] == {"n": 512, "nb": 64, "ib": 16}
    assert config["sizes"] == {"nb": 2048, "ib": 128, "dtype": "float32"}
    assert workload["traffic"] == {"n": 32768, "nb": 2048, "ib": 128}
    assert config["reduced"] == [] and config["nb_cores"] == 4
    assert config["knobs"] == {}
    assert config["taskpool"].endswith(":build_getrf_1d")
    assert callable(MAN.driver(config["driver"]).build)
    correct = config["correct"]
    assert 0 < correct["solve_limit"] < correct["limit"] < 1e-2
    assert 1.0 <= correct["multipliers_limit"] < 1.00001
    assert 0 < correct["low_bits_limit"] < 0.5
    # at least ten times under the incpiv cell's limits: what the digits
    # of partial pivoting are
    incpiv = MAN.config("dgetrf_incpiv_ptg_host")["correct"]
    assert correct["limit"] * 10 <= incpiv["limit"]
    assert correct["solve_limit"] * 10 <= incpiv["solve_limit"]
    for word in ("bfloat16", "4 times", "SWPBACK", "first tile",
                 "another step"):
        assert word in correct["reason"], word
    for word in ("P A0 = L U", "dgetrs", "tpu0", "fresh",
                 "read by the host", "transfer guard", "ALL of L",
                 "storage of A and IPIV"):
        assert word in config["guarantees"], word
    for key in ("nb", "ib", "nb_cores", "precision", "priorities",
                "from_memory", "kernels", "swpback", "one_d_distribution"):
        assert config["assumed"][key]
    assert "LAPACK" in config["ipiv_format"]
    assert "six" in config["precision"]
    assert 1.0 < config["storage"]["peak_over_stored_limit"] < 2.0
    assert "program text" in config["storage"]["reason"]
    # the traffic as ISSUE 43 names it
    d = _driver({"n": 32768, "nb": 2048, "ib": 128})
    assert d.tasks_by_class == {"GETRF": 16, "SWPTRSM": 120, "GEMM": 1240,
                                "SWPBACK": 120}
    assert d.tasks_per_step == 1496
    assert d.ops_per_step == pytest.approx(23.46e12, rel=2e-3)
    assert d.stored_bytes == (4 << 30) + 16 * 2048 * 4


def test_the_operation_counts_against_a_count_by_hand():
    nt, nb = 4, 8
    n = nt * nb
    assert ops_getrf_1d.getrf_ops(n) == ops_getrf.getrf_ops(n) == \
        2.0 * n ** 3 / 3.0 - n * n / 2.0 - n / 6.0
    assert ops_getrf_1d.getrf_1d_tasks(nt) == {
        "GETRF": 4, "SWPTRSM": 6, "GEMM": 9 + 4 + 1, "SWPBACK": 6}
    assert ops_getrf_1d.getrf_1d_stored_bytes(n, 4) == 4 * n * n + 4 * n
    assert ops_getrf_1d.getrf_1d_min_bytes(n, 4) == 8 * n * n + 4 * n
    # a panel of r rows: sum over the columns j of (r - j - 1) divisions
    # and 2 (r - j - 1)(nb - j - 1) update operations, to leading order
    for rows in (8, 16, 32):
        by_hand = sum((rows - j - 1) + 2 * (rows - j - 1) * (nb - j - 1)
                      for j in range(nb))
        assert ops_getrf_1d.panel_ops(rows, nb) == \
            pytest.approx(by_hand, rel=0.25)
    kernels = ops_getrf_1d.getrf_1d_kernels(nt, nb, 4)
    tile, piv = nb * nb * 4, nb * 4
    assert kernels["GEMM"] == (2.0 * nb ** 3, 4 * tile)
    assert kernels["SWPTRSM"] == (1.0 * nb ** 3, 5 * tile + piv)
    assert kernels["SWPBACK"] == (0.0, 4 * tile + piv)
    heights = [4, 3, 2, 1]
    assert kernels["GETRF"][0] == pytest.approx(
        sum(r * nb * nb * nb - nb ** 3 / 3 for r in heights) / 4)
    assert kernels["GETRF"][1] == sum(2 * r * tile + piv
                                      for r in heights) / 4
    # the classes' operations sum to the whole's leading term
    tasks = ops_getrf_1d.getrf_1d_tasks(nt)
    total = sum(tasks[c] * kernels[c][0] for c in tasks)
    assert total == pytest.approx(2.0 * n ** 3 / 3.0, rel=0.02)


# -- the plain reference ------------------------------------------------------

@pytest.mark.parametrize("nt,nb", [(1, 8), (3, 8), (4, 16)])
def test_the_reference_against_numpy_and_lapack(nt, nb):
    import jax
    import jax.numpy as jnp
    n = nt * nb
    key = generate.step_key(11, 3)
    a0 = REF.dense_a0(key, nt, nb)
    assert a0.shape == (n, n) and np.abs(a0).max() <= 0.5
    lu, piv = REF.factor_plain(a0)
    want_lu, want_piv = scipy.linalg.lu_factor(a0.astype(np.float64))
    assert (piv == want_piv).all()
    np.testing.assert_allclose(lu, want_lu, atol=1e-4)
    a = lambda i, j: jnp.asarray(                               # noqa: E731
        lu[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
    p = lambda k: jnp.asarray(                                  # noqa: E731
        (piv[k * nb:(k + 1) * nb] - k * nb)[None, :].astype(np.int32))
    x = np.asarray(REF.probe_vectors(key, n))
    low = np.tril(lu, -1).astype(np.float64) + np.eye(n)
    up = np.triu(lu).astype(np.float64)
    with jax.default_matmul_precision("highest"):
        xj = jnp.asarray(x)
        np.testing.assert_allclose(REF.apply_l(a, xj, nt), low @ x,
                                   atol=1e-4)
        np.testing.assert_allclose(REF.apply_u(a, xj, nt), up @ x,
                                   atol=1e-4)
        np.testing.assert_allclose(
            REF.solve_l(a, jnp.asarray((low @ x).astype(np.float32)), nt),
            x, atol=1e-3)
        np.testing.assert_allclose(
            REF.solve_u(a, jnp.asarray((up @ x).astype(np.float32)), nt),
            x, rtol=1e-2, atol=1e-2 * np.linalg.cond(up) * 1e-4)
        px = x.copy()
        for j, q in enumerate(piv):
            px[[j, q]] = px[[q, j]]
        assert (np.asarray(REF.apply_p(p, xj, nt)) == px).all()
        assert (np.asarray(REF.apply_pt(p, jnp.asarray(px), nt)) == x).all()
        # P A0 = L U, as the check reads it
        np.testing.assert_allclose(
            REF.apply_l(a, REF.apply_u(a, xj, nt), nt),
            REF.apply_p(p, jnp.asarray(a0 @ x), nt), atol=1e-4)
    assert all(bool(REF.pivots_valid(k, p(k), nt)) for k in range(nt))
    assert not bool(REF.pivots_valid(0, p(0).at[0, 3].set(nt * nb), nt))
    assert not bool(REF.pivots_valid(0, p(0).at[0, 3].set(2), nt))
    assert max(float(REF.multipliers(i, j, a(i, j)))
               for j in range(nt) for i in range(j, nt)) <= 1.0


def test_the_input_is_the_incpiv_cells_for_the_same_seed():
    import jax.numpy as jnp
    other = MAN.reference("dgetrf_incpiv_ptg_host_reference")
    key = generate.step_key(123, 4)
    assert np.array_equal(REF.dense_a0(key, 3, 8), other.dense_a0(key, 3, 8))
    x = REF.probe_vectors(key, 24)
    y, sq = jnp.zeros_like(x), jnp.zeros(())
    for i in range(3):
        y, sq = REF.probe_input_row(i, key, x, y, sq, nt=3, nb=8)
    a0 = REF.dense_a0(key, 3, 8)
    np.testing.assert_allclose(y, a0 @ np.asarray(x), atol=1e-5)
    assert float(sq) == pytest.approx(float((a0 * a0).sum()), rel=1e-5)


# -- the driver's check, and what fails it -----------------------------------

def _first_tile_only(a0, nb):
    """P A = L U with every pivot sought within the panel's FIRST tile
    only (what a tile-local kernel would give): a valid factorization
    whose multipliers in the tiles below are not bounded by 1."""
    a = np.array(a0, np.float64)
    n = a.shape[0]
    ipiv = np.zeros(n, np.int32)
    for j in range(n):
        k = j // nb
        p = j + int(np.argmax(np.abs(a[j:(k + 1) * nb, j])))
        ipiv[j] = p
        a[[j, p]] = a[[p, j]]
        a[j + 1:, j] /= a[j, j]
        a[j + 1:, j + 1:] -= np.outer(a[j + 1:, j], a[j, j + 1:])
    return a.astype(np.float32), ipiv


def _write(d, lu, piv):
    """A dense factored form into the driver's collections, on the chip."""
    import jax
    import jax.numpy as jnp
    nb, nt = d.nb, d.nt
    here = jax.sharding.SingleDeviceSharding(d.devices[0])
    for i in range(nt):
        for j in range(nt):
            d.A.write_tile((i, j), jax.device_put(jnp.asarray(
                lu[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]), here))
    for k in range(nt):
        d.IPIV.write_tile((k, 0), jax.device_put(jnp.asarray(
            (piv[k * nb:(k + 1) * nb] - k * nb)[None, :].astype(np.int32)),
            here))


def test_the_factored_form_passes_and_each_sabotage_fails():
    import jax
    import jax.numpy as jnp
    n, nb, ib = 128, 32, 16
    nt = n // nb
    d = _driver({"n": n, "nb": nb, "ib": ib})
    bf16 = jax.jit(lambda t: jax.lax.reduce_precision(
        t, exponent_bits=8, mantissa_bits=7))
    try:
        facts = d.setup()
        assert set(facts["kernels"]) == {"GETRF", "SWPTRSM", "GEMM",
                                         "SWPBACK"}
        assert facts["kernels"]["GEMM"][0] == 14
        a = d.generate(2)
        a0 = REF.dense_a0(generate.step_key(5, 2), nt, nb)
        for i, j in a.keys():
            t = a.data_of((i, j))
            assert t.committed
            assert np.array_equal(np.asarray(t), a0[
                i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
        assert all(d.IPIV.data_of((k, 0)).dtype == jnp.int32 and
                   d.IPIV.data_of((k, 0)).committed for k in range(nt))
        before = d.counters()
        a = d.step(a)
        after = d.counters()
        assert d.tasks_per_step == 4 + 6 + 14 + 6
        assert after["tasks_by_module"]["tpu0"] - \
            before["tasks_by_module"]["tpu0"] == 30 == \
            sum(after["tasks_by_module"].values())
        # the program's counters over the window, for the readers: the
        # sum of the list lengths, every launch in place
        assert d.window_counters["ranged_tiles_staged"] == 10 + 20 + 10
        assert d.window_counters["ranged_scatters"] > 0
        assert d.window_counters["lone_in_place"] + \
            d.window_counters["groups_in_place"] > 0
        assert d.ops_per_step == ops_getrf_1d.getrf_ops(n)
        assert d.bytes_per_step == ops_getrf_1d.getrf_1d_min_bytes(n, 4)
        got = d.readings(a, 2)
        assert set(got) == {"residual", "solve", "multipliers", "low_bits",
                            "pivots_valid"}
        assert got["residual"] < 1e-5 and got["solve"] < 1e-6
        assert got["multipliers"] <= 1.0 and got["pivots_valid"]
        # LAPACK's form: IPIV entry for entry
        lu = np.block([[np.asarray(a.data_of((i, j))) for j in range(nt)]
                       for i in range(nt)])
        piv = np.concatenate([np.asarray(d.IPIV.data_of((k, 0)))[0] + k * nb
                              for k in range(nt)])
        _want, want_piv = scipy.linalg.lu_factor(a0.astype(np.float64))
        assert (piv == want_piv).all()
        # held to limits near what the chip reads
        d.config = dict(d.config, correct={
            "limit": 1e-5, "solve_limit": 1e-6,
            "multipliers_limit": 1.000001, "low_bits_limit": 0.01})
        ok, detail = d.check(a, 2)
        assert ok and detail["factored_form_on_chip"]
        # 1. a tile of U, of L, a diagonal tile rounded to bfloat16
        for key in ((1, 2), (2, 1), (1, 1)):
            good = a.data_of(key)
            a.write_tile(key, bf16(good))
            ok, detail = d.check(a, 2)
            assert not ok and detail["low_bits"] > detail["low_bits_limit"]
            a.write_tile(key, good)
        assert d.check(a, 2)[0]
        # 2. every panel pivoted within its first tile only: P A = L U
        #    still holds; a multiplier over 1 in a tile below tells
        _write(d, *_first_tile_only(a0, nb))
        ok, detail = d.check(a, 2)
        assert not ok and detail["pivots_valid"]
        assert detail["multipliers"] > detail["multipliers_limit"]
        assert detail["residual"] < 1e-3    # a factorization all the same
        # 3. SWPBACK not applied: the later panels' interchanges undone
        #    in the columns on their left
        unswapped = lu.copy()
        for k in reversed(range(1, nt)):
            for j in reversed(range(k * nb, (k + 1) * nb)):
                rows = [j, piv[j]]
                unswapped[rows, :k * nb] = unswapped[rows[::-1], :k * nb]
        assert not np.array_equal(unswapped, lu)
        _write(d, unswapped, piv)
        ok, detail = d.check(a, 2)
        assert not ok and detail["residual"] > detail["residual_limit"]
        assert detail["multipliers"] <= 1.0 and detail["pivots_valid"]
        _write(d, lu, piv)
        assert d.check(a, 2)[0]
        # 4. an index out of range, and one before its own step
        good = d.IPIV.data_of((1, 0))
        for bad in ((nt - 1) * nb, 4):
            d.IPIV.write_tile((1, 0), good.at[0, 5].set(bad))
            ok, detail = d.check(a, 2)
            assert not ok and not detail["pivots_valid"]
        d.IPIV.write_tile((1, 0), good)
        # 5. another step's matrix
        ok, detail = d.check(a, 3)
        assert not ok and detail["residual"] > detail["residual_limit"]
        # a wrong tile of U; a tile on the host; a non-finite tile
        good = a.data_of((1, 2))
        a.write_tile((1, 2), good + 0.5)
        ok, detail = d.check(a, 2)
        assert not ok and detail["residual"] > detail["residual_limit"]
        a.write_tile((1, 2), np.asarray(good))
        ok, detail = d.check(a, 2)
        assert not ok and not detail["factored_form_on_chip"]
        a.write_tile((1, 2), jnp.full((nb, nb), jnp.nan))
        assert not d.finite(a)
        a.write_tile((1, 2), good)
        assert d.check(a, 2)[0]
        # a step whose tasks were not all counted on the chip's module
        d.steps_run += 1
        ok, detail = d.check(a, 2)
        assert not ok and detail["tasks_on_chip"] == 30 and \
            detail["tasks_of_the_steps"] == 60
    finally:
        d.close()


def test_the_storage_guarantee_stops_a_program_that_holds_tiles_twice(
        monkeypatch):
    """6. a broken storage guarantee fails the check, and the warm step."""
    d = _driver({"n": 128, "nb": 32, "ib": 16})
    stored = 4 * (128 * 128 + 128)
    assert d.stored_bytes == stored
    limit = MAN.config(CONFIG)["storage"]["peak_over_stored_limit"]
    assert d.storage_limit_bytes == pytest.approx(limit * stored)
    peak = [int(1.05 * stored)]
    try:
        d.setup()
        assert d._peak_bytes() == 0         # the CPU keeps no such count
        monkeypatch.setattr(d, "_peak_bytes", lambda: peak[0])
        a = d.step(d.generate(0))           # the warm step, within it
        ok, detail = d.check(a, 0)
        assert ok and detail["peak_bytes"] == peak[0] < \
            detail["storage_limit_bytes"]
        peak[0] = int(2.0 * stored)
        ok, detail = d.check(a, 0)
        assert not ok and detail["residual"] <= detail["residual_limit"]
        d.steps_run = 0
        with pytest.raises(RuntimeError, match="own storage"):
            d.step(d.generate(0))
    finally:
        d.close()


def test_a_step_runs_under_the_transfer_guard(monkeypatch):
    """The warm step and every step of a rehearsal run with device-to-host
    transfers disallowed in the whole process, and the setting is put
    back when the step ends."""
    import jax
    from benchmark.drivers.ptg_factorization import PtgFactorization
    d = _driver({"n": 64, "nb": 32, "ib": 16})
    seen = []
    try:
        d.setup()
        real = PtgFactorization.step

        def step(self, A):
            seen.append(jax.config.jax_transfer_guard_device_to_host)
            return real(self, A)

        monkeypatch.setattr(PtgFactorization, "step", step)
        was = jax.config.jax_transfer_guard_device_to_host
        d.step(d.generate(0))
        assert seen == ["disallow"]
        assert jax.config.jax_transfer_guard_device_to_host == was
    finally:
        d.close()


def test_a_tree_without_the_builder_stops_before_a_context_starts(
        monkeypatch):
    """The parent's ``getrf.py`` has no ``build_getrf_1d``: set-up exits
    at once with a message, and ``close`` has no Context to stop."""
    import parsec_tpu.algorithms.getrf as getrf
    monkeypatch.delattr(getrf, "build_getrf_1d")
    d = _driver({"n": 128, "nb": 32, "ib": 16})
    with pytest.raises(SystemExit, match="cannot run dgetrf_1d_ptg_host"):
        d.setup()
    assert d.ctx is None
    d.close()


def test_the_kernels_open_the_three_scopes():
    import jax
    import jax.numpy as jnp
    from parsec_tpu.ops import tile_kernels as tk
    t = jnp.zeros((32, 32), jnp.float32)
    piv = jnp.zeros((1, 32), jnp.int32)
    texts = {
        "GETRF": jax.jit(lambda ts: tk.getrf_panel_tiles(ts, 16)).lower(
            [t, t]).as_text(debug_info=True),
        "SWPTRSM": jax.jit(tk.swptrsm_tiles).lower(t, piv, [t, t]).as_text(
            debug_info=True),
        "GEMM": jax.jit(tk.gemm_full_tile).lower(t, t, t).as_text(
            debug_info=True),
        "SWPBACK": jax.jit(tk.laswp_tiles).lower([t, t], piv).as_text(
            debug_info=True)}
    for scope in ("parsec:lu_pivot", "parsec:lu_swap", "parsec:lu_update"):
        assert scope in texts["GETRF"], scope
    assert "parsec:lu_swap" in texts["SWPTRSM"]
    assert "parsec:lu_update" in texts["SWPTRSM"]
    assert "parsec:lu_update" in texts["GEMM"]
    assert "parsec:lu_swap" in texts["SWPBACK"]


# -- the rehearsals ----------------------------------------------------------

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


def _dry_run(capsys, root, trace):
    # a traced window long enough for steady steps under six test workers
    rc = main(["--workload", CELL, "--seed", "4300000043", "--seconds",
               "4.0" if trace else "1.0", "--trace", str(trace),
               "--dry-run-cpu=1"], root=root)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


def test_the_traced_rehearsal_prints_every_new_metric_a_cpu_can_read(
        capsys, checkout):
    last, lines = _dry_run(capsys, checkout, 1)
    assert last["correct"] is True and last["failed"] == 0
    got = {n[:-len(DRY_SUFFIX)]: m for n, m in last["metrics"].items()}
    # all but those that read the device's plane of the trace
    assert set(NEW) - DEVICE_TRACE <= set(got)
    assert not DEVICE_TRACE & set(got)
    for name in set(NEW) - DEVICE_TRACE:
        assert got[name]["unit"] == NEW[name][0] and got[name]["value"] >= 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["lu1d_tasks_on_chip_share"]["value"] == 100.0
    assert 1.0 <= got["lu1d_tasks_per_launch"]["value"] <= 204
    # the sum of the list lengths over the tasks, NT = 8: 288 / 204
    assert got["lu1d_ranged_tiles_per_task"]["value"] == \
        pytest.approx(288 / 204)
    check = [line for line in lines if line.startswith("[check]")][0]
    for word in ("residual=", "solve=", "multipliers=", "low_bits=",
                 "pivots_valid=True", "factored_form_on_chip=True"):
        assert word in check
    window = [line for line in lines if line.startswith("[window]")][0]
    for cls in ("GETRF", "SWPTRSM", "GEMM", "SWPBACK"):
        assert f"'tasks.{cls}'" in window and f"'launches.{cls}'" in window
    for name in ("ranged_tiles_staged", "ranged_launches", "ranged_scatters",
                 "lone_in_place"):
        assert f"'{name}'" in window


def test_the_untraced_rehearsal_prints_the_end_to_end_metrics(capsys,
                                                              checkout):
    last, _lines = _dry_run(capsys, checkout, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert {n + DRY_SUFFIX for n in ("tflops_per_chip", "step_s_p50",
                                     "setup_s")} <= set(last["metrics"])
