"""The ``dgetrf_incpiv_ptg_host`` configuration and its cell: that what
``BENCHMARK.json`` gained for them is declared and resolves (found by
name; a reader needs a metric declared and a number from a rehearsal's
record, not the exact set of a cell's metrics), the plain reference
against ``numpy`` at test size, the operation counts, the driver's check
(a wrong tile, a U, an L and a diagonal tile rounded to bfloat16, an
invalid pivot, a broken storage guarantee and another step's matrix all
fail it), the two new readers on hand-made records and a hand-made
``.xplane.pb``, and the rehearsals, which print every new metric under
its ``_cpu_dryrun`` name. The cell's other CPU dry runs come through
``test_benchmark_dryrun.py``'s parametrisation."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate, ops, ops_getrf  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, Spans, main  # noqa: E402

MAN = Manifest(ROOT)
CELL, CONFIG = "getrf_incpiv_ptg_host_n32768_nb2048", \
    "dgetrf_incpiv_ptg_host"
REF = MAN.reference("dgetrf_incpiv_ptg_host_reference")
SCOPES = MAN.reader("device_seconds_by_scope")
# name -> (unit, source, moves, reader)
NEW = {
    "lu_host_us_per_task": ("us", "host_clock", "step_s_p50",
                            "host_us_per_task"),
    "lu_tasks_per_launch": ("ratio", "program_span", "step_s_p50",
                            "tasks_per_launch"),
    "lu_tasks_on_chip_share": ("%", "program_counter", "tflops_per_chip",
                               "tasks_on_chip_share"),
    "lu_device_step_s": ("s", "device_trace", "step_s_p50",
                         "device_step_s"),
    "lu_tile_roofline": ("%", "device_trace", "tflops_per_chip",
                         "roofline"),
    "lu_ssssm_roofline": ("%", "device_trace", "tflops_per_chip",
                          "device_seconds_by_program"),
    "lu_tstrf_roofline": ("%", "device_trace", "tflops_per_chip",
                          "device_seconds_by_program"),
    "lu_panel_kernel_share": ("%", "device_trace", "step_s_p50",
                              "device_seconds_by_program"),
    "lu_swap_share": ("%", "device_trace", "step_s_p50",
                      "device_seconds_by_scope"),
    "lu_int_tiles_per_task": ("ratio", "program_counter", "step_s_p50",
                              "counter_per_task"),
}
DEVICE_TRACE = {n for n, spec in NEW.items() if spec[1] == "device_trace"}


def _named(section, name):
    (entry,) = [e for e in MAN.bench[section] if e["name"] == name]
    return entry


# -- what BENCHMARK.json gained, and that it resolves ------

def test_the_configuration_and_the_cell_are_declared():
    config = _named("configs", CONFIG)
    assert config["file"] == "benchmark/configs/dgetrf_incpiv_ptg_host.json"
    assert config["reduced"] == [] and config["why"]
    source = config["source"]
    assert "dplasma/blob/master/src/zgetrf_incpiv.jdf" in source
    assert "testing_zgetrf_incpiv.c" in source and len(source) <= 200
    assert "-N <n> -t <NB> -i <IB>" in source
    assert source == MAN.config(CONFIG)["source"]
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MAN.bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    # the end-to-end metrics every cell reports
    assert {"tflops_per_chip", "step_s_p50", "peak_hbm_gib", "setup_s"} <= {
        m["name"] for m in MAN.metrics_for("end_to_end", CELL)}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_declared_for_the_cell_and_resolves(name):
    unit, source, moves, reader = NEW[name]
    entry = _named("per_layer", name)
    assert (entry["unit"], entry["source"], entry["moves"]) == \
        (unit, source, moves)
    assert entry["workloads"] == [CELL]
    assert entry["layer"] in {m["layer"] for m in MAN.bench["per_layer"]
                              if not m["name"].startswith("lu_")}
    assert name in {m["name"] for m in MAN.metrics_for("per_layer", CELL)}
    spec = MAN.metric(name)
    assert spec["name"] == name and spec["what"] and spec["reader"] == reader
    assert callable(MAN.reader(reader).read)
    if name.endswith("_roofline"):
        assert unit == "%"


def test_the_configuration_file_states_the_deployment():
    config = MAN.config(CONFIG)
    workload = MAN.workload(CELL)
    assert workload["dry"] == {"n": 512, "nb": 64, "ib": 16}
    sizes = config["sizes"]
    assert sizes["nb"] == 2048 and sizes["dtype"] == "float32"
    assert sizes["ib"] in (128, 256, 512)
    # the cell restates the configuration's IB (a rehearsal's `dry` sizes
    # may only name what the traffic names)
    assert workload["traffic"] == {"n": 32768, "nb": 2048,
                                   "ib": sizes["ib"]}
    assert config["reduced"] == [] and config["nb_cores"] == 4
    assert config["taskpool"].endswith(":build_getrf_incpiv")
    assert callable(MAN.driver(config["driver"]).build)
    correct = config["correct"]
    assert 0 < correct["solve_limit"] < correct["limit"] < 0.2
    assert 1.0 <= correct["multipliers_limit"] < 1.00001
    assert 0 < correct["low_bits_limit"] < 0.5
    for word in ("bfloat16", "4 times", "U tile", "L(m,k)", "diagonal"):
        assert word in correct["reason"], word
    for word in ("A0 = M U", "dgetrs_incpiv", "tpu0", "fresh",
                 "read by the host", "transfer guard",
                 "storage of A, L and IPIV"):
        assert word in config["guarantees"], word
    for key in ("nb", "ib", "nb_cores", "precision", "priorities",
                "from_memory", "kernels"):
        assert config["assumed"][key]
    assert "LAPACK" in config["ipiv_format"]
    assert "permutation" in config["ipiv_format"]
    assert 1.0 < config["storage"]["peak_over_stored_limit"] < 2.0
    assert "travel" in config["storage"]["reason"]
    # the traffic as ISSUE 39 names it
    d = _driver({"n": 32768, "nb": 2048, "ib": sizes["ib"]})
    assert d.tasks_by_class == {"GETRF": 16, "GESSM": 120, "TSTRF": 120,
                                "SSSSM": 1240}
    assert d.tasks_per_step == 1496
    assert d.ops_per_step == pytest.approx(23.46e12, rel=2e-3)
    assert d.stored_bytes == (4 << 30) + 120 * sizes["ib"] * 2048 * 4 + \
        136 * 2048 * 4


def test_the_operation_counts_are_the_kernels_sums():
    for n, nb, ib in ((32768, 2048, 512), (8192, 1024, 128), (512, 64, 16)):
        nt = n // nb
        tasks = ops_getrf.getrf_tasks(nt)
        kernels = ops_getrf.getrf_kernels(nb, ib, 4)
        assert set(tasks) == set(kernels) == {"GETRF", "GESSM", "TSTRF",
                                              "SSSSM"}
        summed = sum(tasks[c] * kernels[c][0] for c in tasks)
        assert summed == pytest.approx(2.0 * n ** 3 / 3.0, rel=1e-12)
        assert ops_getrf.getrf_ops(n) == pytest.approx(
            summed, rel=1.0 / n + 1e-9)
        assert ops_getrf.getrf_ops(n) < summed
        assert ops_getrf.getrf_min_bytes(n, nb, ib, 4) == 4 * (
            2 * n * n + nt * (nt - 1) // 2 * ib * nb
            + nt * (nt + 1) // 2 * nb)
        # an SSSSM reads L21, L, IPIV, A1, A2 and writes A1, A2
        assert kernels["SSSSM"][1] == 4 * (5 * nb * nb + ib * nb + nb)
    # the cell: compute-bound, 0.119 s at the v5e's peaks
    peaks = MAN.peaks("TPU v5 lite")
    least, bound = ops.roofline_seconds(
        ops_getrf.getrf_ops(32768),
        ops_getrf.getrf_min_bytes(32768, 2048, 512, 4),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    assert bound == "compute" and least == pytest.approx(0.119, rel=3e-3)


# -- the plain reference ------

@pytest.mark.parametrize("nt,nb,ib", [(3, 16, 16), (3, 16, 4), (1, 16, 8)])
def test_the_plain_loops_against_numpy(nt, nb, ib):
    """``factor_plain``'s factored form, read back by ``apply_l`` and
    ``solve_u``, solves A0 x = b as ``numpy.linalg.solve`` does, and
    A0 = M U."""
    import jax
    import jax.numpy as jnp
    n = nt * nb
    key = generate.step_key(5, 2)
    a0 = REF.dense_a0(key, nt, nb)
    assert a0.dtype == np.float32 and a0.shape == (n, n)
    assert -0.5 <= a0.min() < a0.max() < 0.5
    a, low, piv = ([lambda i, j, d=d: jnp.asarray(d[i, j])
                    for d in REF.factor_plain(a0, nb, ib)])
    x = np.asarray(REF.probe_vectors(key, n))
    with jax.default_matmul_precision("highest"):
        u = np.triu(np.block([[np.asarray(a(i, j)) for j in range(nt)]
                              for i in range(nt)]))
        mux = REF.apply_l_inverse(a, low, piv, jnp.asarray(u @ x), nt)
        np.testing.assert_allclose(mux, a0 @ x, rtol=1e-4, atol=1e-4)
        # apply_l undoes apply_l_inverse
        np.testing.assert_allclose(
            REF.apply_l(a, low, piv, mux, nt), u @ x, rtol=1e-4, atol=1e-4)
        got = REF.solve_u(a, REF.apply_l(a, low, piv,
                                         jnp.asarray(a0 @ x), nt), nt)
        want = np.linalg.solve(a0.astype(np.float64), (a0 @ x))
        tol = 1e-6 * np.linalg.cond(a0.astype(np.float64))
        assert np.linalg.norm(np.asarray(got) - want) <= \
            tol * np.linalg.norm(want)
        # U x from the tiles: a diagonal tile's lower part is not read
        ux = jnp.zeros((n, x.shape[1]), jnp.float32)
        for i in range(nt):
            for j in range(i, nt):
                ux = REF.probe_u(i, j, a(i, j), jnp.asarray(x), ux)
        np.testing.assert_allclose(ux, u @ x, rtol=1e-4, atol=1e-4)
    for k in range(nt):
        assert bool(REF.permutation_valid(piv(k, k)))
        assert float(REF.multipliers_diagonal(a(k, k))) <= 1.0
        for m in range(k + 1, nt):
            assert bool(REF.interchanges_valid(piv(m, k), ib))
            assert float(REF.multipliers_pair(a(m, k), low(m, k))) <= 1.0


def test_the_input_is_rebuilt_a_block_row_at_a_time():
    import jax.numpy as jnp
    nt, nb = 3, 16
    key = generate.step_key(5, 2)
    a0 = REF.dense_a0(key, nt, nb)
    for i in range(nt):
        for j in range(nt):
            assert np.array_equal(
                a0[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                np.asarray(generate.tile(key, i * nt + j, nb), np.float32))
    x = REF.probe_vectors(key, nt * nb)
    y, sq = jnp.zeros_like(x), jnp.zeros((), jnp.float32)
    for i in range(nt):
        y, sq = REF.probe_input_row(i, key, x, y, sq, nt=nt, nb=nb)
    np.testing.assert_allclose(y, a0 @ np.asarray(x), rtol=1e-4, atol=1e-4)
    assert float(sq) == pytest.approx(float((a0.astype(np.float64) ** 2).sum()),
                                      rel=1e-5)


def test_what_a_bfloat16_holds_exactly_shows_in_the_low_bits():
    import jax
    import jax.numpy as jnp
    t = jnp.asarray(np.random.default_rng(1).uniform(
        -0.5, 0.5, (256, 256)).astype(np.float32))
    assert float(REF.low_bits_share(t)) < 1e-3
    rounded = jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    assert float(REF.low_bits_share(rounded)) == 1.0
    # zeros and ones (an L11's upper part and diagonal) are not counted
    assert float(REF.low_bits_share(jnp.eye(8, dtype=jnp.float32))) == 0.0


# -- the driver ------

def _driver(sizes, seed=5):
    import jax
    config = MAN.config(CONFIG)
    return MAN.driver(config["driver"]).build(
        config, {**config["sizes"], **sizes}, seed, jax.devices()[:1],
        Spans(), REF)


def test_the_factored_form_passes_and_a_wrong_or_rounded_tile_fails():
    import jax
    import jax.numpy as jnp
    n, nb, ib = 128, 32, 16
    d = _driver({"n": n, "nb": nb, "ib": ib})
    bf16 = jax.jit(lambda t: jax.lax.reduce_precision(
        t, exponent_bits=8, mantissa_bits=7))
    try:
        facts = d.setup()
        assert set(facts["kernels"]) == {"GETRF", "GESSM", "TSTRF", "SSSSM"}
        assert facts["kernels"]["SSSSM"][0] == 14
        a = d.generate(2)
        a0 = REF.dense_a0(generate.step_key(5, 2), 4, nb)
        for i, j in a.keys():
            t = a.data_of((i, j))
            assert t.committed
            assert np.array_equal(np.asarray(t), a0[
                i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
        # L's and IPIV's storage lies on the chip before the first step
        assert all(d.IPIV.data_of(k).dtype == jnp.int32 and
                   d.IPIV.data_of(k).committed for k in d.p_keys)
        before = d.counters()
        a = d.step(a)
        after = d.counters()
        assert d.tasks_per_step == 4 + 6 + 6 + 14
        assert after["tasks_by_module"]["tpu0"] - \
            before["tasks_by_module"]["tpu0"] == 30 == \
            sum(after["tasks_by_module"].values())
        # the chip module's counters over the window, for the readers
        assert d.window_counters["int_tiles_staged"] >= 16
        assert d.window_counters["region_merges"] == 3
        assert d.ops_per_step == ops_getrf.getrf_ops(n)
        assert d.bytes_per_step == ops_getrf.getrf_min_bytes(n, nb, ib, 4)
        got = d.readings(a, 2)
        assert set(got) == {"residual", "solve", "multipliers", "low_bits",
                            "pivots_valid"}
        assert got["residual"] < 1e-4 and got["solve"] < 1e-5
        assert got["multipliers"] <= 1.0 and got["pivots_valid"]
        # held to limits near what the chip reads
        d.config = dict(d.config, correct={
            "limit": 1e-4, "solve_limit": 1e-5,
            "multipliers_limit": 1.000001, "low_bits_limit": 0.01})
        ok, detail = d.check(a, 2)
        assert ok and detail["factored_form_on_chip"]
        # the check is of THIS step's input: another step's key fails it
        assert not d.check(a, 3)[0]
        # a U tile, an L(m,k) tile, a diagonal tile rounded to bfloat16
        for coll, key in ((a, (1, 2)), (d.L, (2, 1)), (a, (1, 1))):
            good = coll.data_of(key)
            coll.write_tile(key, bf16(good))
            ok, detail = d.check(a, 2)
            assert not ok and detail["low_bits"] > detail["low_bits_limit"]
            coll.write_tile(key, good)
            assert d.check(a, 2)[0]
        good = a.data_of((1, 2))                # a tile of U
        a.write_tile((1, 2), good + 0.5)
        ok, detail = d.check(a, 2)
        assert not ok and detail["residual"] > detail["residual_limit"]
        a.write_tile((1, 2), good)
        good = a.data_of((2, 1))                # a multiplier over 1
        a.write_tile((2, 1), good.at[3, 4].set(1.5))
        ok, detail = d.check(a, 2)
        assert not ok and detail["multipliers"] > detail["multipliers_limit"]
        a.write_tile((2, 1), good)
        good = d.IPIV.data_of((2, 1))           # an index out of range
        d.IPIV.write_tile((2, 1), good.at[0, 5].set(ib + nb))
        ok, detail = d.check(a, 2)
        assert not ok and not detail["pivots_valid"]
        d.IPIV.write_tile((2, 1), good)
        good = a.data_of((1, 2))
        a.write_tile((1, 2), np.asarray(good))      # right, but on the host
        ok, detail = d.check(a, 2)
        assert not ok and not detail["factored_form_on_chip"]
        a.write_tile((1, 2), jnp.full((nb, nb), jnp.nan))
        assert not d.finite(a)
        # a step whose tasks were not all counted on the chip's module
        a.write_tile((1, 2), good)
        assert d.check(a, 2)[0]
        d.steps_run += 1
        ok, detail = d.check(a, 2)
        assert not ok and detail["tasks_on_chip"] == 30 and \
            detail["tasks_of_the_steps"] == 60
    finally:
        d.close()


def test_the_storage_guarantee_stops_a_program_that_holds_tiles_twice(
        monkeypatch):
    d = _driver({"n": 128, "nb": 32, "ib": 16})
    stored = 4 * (128 * 128 + 6 * 16 * 32 + 10 * 32)
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


def test_a_host_read_of_a_tile_inside_a_step_fails_the_step(monkeypatch):
    """The warm step and every step of a rehearsal run with device-to-host
    transfers disallowed in the whole process, and the setting is put
    back when the step ends."""
    import jax
    d = _driver({"n": 64, "nb": 32, "ib": 16})
    seen = []
    try:
        d.setup()
        base = type(d).__mro__[1]
        real = base.step

        def step(self, A):
            seen.append(jax.config.jax_transfer_guard_device_to_host)
            return real(self, A)

        monkeypatch.setattr(base, "step", step)
        was = jax.config.jax_transfer_guard_device_to_host
        d.step(d.generate(0))
        assert seen == ["disallow"]
        assert jax.config.jax_transfer_guard_device_to_host == was
    finally:
        d.close()


def test_a_tree_without_the_pivoted_builder_stops_before_a_context_starts(
        monkeypatch):
    """The parent's ``getrf.py`` has no ``build_getrf_incpiv``: set-up
    raises at once, and ``close`` has no Context to stop."""
    import parsec_tpu.algorithms.getrf as getrf
    monkeypatch.delattr(getrf, "build_getrf_incpiv")
    d = _driver({"n": 128, "nb": 32, "ib": 16})
    with pytest.raises(AttributeError):
        d.setup()
    assert d.ctx is None
    d.close()


# -- the new readers ------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _xspace(ops, plane="/device:TPU:0"):
    """A hand-made ``.xplane.pb``: ``ops``: ``[(name stack, start_ps,
    duration_ps)]`` on the plane's ``XLA Ops`` line, the name stacks in
    the events' metadata as the stat ``tf_op``."""
    stat_meta = _field(5, _field(1, 7) + _field(2, _field(1, 7)
                                                + _field(2, "tf_op")))
    metas, events = b"", b""
    for i, (stack, start, dur) in enumerate(ops, start=1):
        meta = _field(1, i) + _field(2, f"%fusion.{i}") + \
            _field(5, _field(1, 7) + _field(5, stack))
        metas += _field(4, _field(1, i) + _field(2, meta))
        events += _field(4, _field(1, i) + _field(2, start)
                         + _field(3, dur))
    line = _field(3, _field(2, "XLA Ops") + events)
    other = _field(3, _field(2, "XLA Modules") + _field(
        4, _field(1, 1) + _field(2, 0) + _field(3, 10 ** 13)))
    return _field(1, _field(2, plane) + other + line + metas + stat_meta)


def test_device_seconds_under_a_named_scope_from_the_files_own_bytes(
        tmp_path):
    s = 10 ** 12                                    # a second, in ps
    ops_ = [("jit(parsec_SSSSM_x1)/parsec:lu_swap/gather:", 10 * s, s // 2),
            ("jit(parsec_SSSSM_x1)/parsec:lu_update/dot_general:",
             11 * s, s),
            ("jit(parsec_TSTRF_x1)/parsec:lu_swap/scatter:", 13 * s, s // 4),
            ("jit(parsec_TSTRF_x1)/parsec:lu_swap/scatter:", 30 * s, s)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops_) + _xspace(ops_[:1], "/host:CPU"))
    found = SCOPES.scope_intervals(str(path), "parsec:lu_swap")
    assert set(found) == {0}                        # the chip's plane alone
    assert sorted(found[0]) == pytest.approx(
        [(10.0, 10.5), (13.0, 13.25), (30.0, 31.0)])
    assert SCOPES.scope_intervals(str(path), "parsec:lu_pivot") == {}
    # a scope is a component of the stack, not a substring of one
    assert SCOPES.scope_intervals(str(path), "parsec:lu") == {}
    bench = [("traced", 0.0, 100.0), ("step", 10.0, 12.0),
             ("step", 12.5, 14.5)]
    assert SCOPES.seconds_in_steps(found, bench) == pytest.approx(0.75)
    assert SCOPES.seconds_in_steps(found, [("step", 10.0, 12.0)]) is None
    assert SCOPES.seconds_in_steps({}, bench) is None


def test_without_a_trace_the_scope_reader_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(SCOPES, "_CHECKOUT", str(tmp_path))
    record = {"cell": CELL, "trace": {}, "setup": {}, "peaks": None}
    assert SCOPES.read(record, MAN.metric("lu_swap_share")["params"]) is None


def test_a_counter_per_task_of_the_windows_steps():
    reader = MAN.reader("counter_per_task")
    params = MAN.metric("lu_int_tiles_per_task")["params"]
    record = {"setup": {"program_counters": {"int_tiles_staged": 2992}},
              "window": {"attempted": 3, "failed": 1},
              "driver": {"tasks_per_step": 1496}}
    assert reader.read(record, params) == 1.0
    # a program without the counter (the parent), a driver that leaves
    # none, no step
    assert reader.read(dict(record, setup={"program_counters": {}}),
                       params) is None
    assert reader.read(dict(record, setup={}), params) is None
    assert reader.read(dict(record, window={"attempted": 1, "failed": 1}),
                       params) is None


def test_the_kernels_open_the_three_scopes():
    """What a device trace splits a class's time by: the name stacks of
    the four kernels' operations hold the scopes."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.ops import tile_kernels as tk
    t = jnp.zeros((32, 32), jnp.float32)
    low, piv = jnp.zeros((16, 32), jnp.float32), jnp.zeros((1, 32), jnp.int32)
    texts = {
        "GETRF": jax.jit(tk.getrf_incpiv_tile).lower(t).as_text(
            debug_info=True),
        "GESSM": jax.jit(tk.gessm_tile).lower(t, piv, t).as_text(
            debug_info=True),
        "TSTRF": jax.jit(lambda u, a: tk.tstrf_tile(u, a, 16)).lower(
            t, t).as_text(debug_info=True),
        "SSSSM": jax.jit(tk.ssssm_tile).lower(t, t, low, t, piv).as_text(
            debug_info=True)}
    assert "parsec:lu_pivot" in texts["GETRF"]
    for cls in ("GESSM", "TSTRF", "SSSSM"):
        assert "parsec:lu_swap" in texts[cls], cls
        assert "parsec:lu_update" in texts[cls], cls
    assert "parsec:lu_pivot" in texts["TSTRF"]


# -- the rehearsals ------

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
    rc = main(["--workload", CELL, "--seed", "3900000019", "--seconds",
               "1.0", "--trace", str(trace), "--dry-run-cpu=1"], root=root)
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
    assert got["lu_tasks_on_chip_share"]["value"] == 100.0
    assert 1.0 <= got["lu_tasks_per_launch"]["value"] <= 204
    # every task reads or writes one int32 tile, a group's members one
    assert 0.2 < got["lu_int_tiles_per_task"]["value"] <= 1.0
    check = [line for line in lines if line.startswith("[check]")][0]
    for word in ("residual=", "solve=", "multipliers=", "low_bits=",
                 "pivots_valid=True", "factored_form_on_chip=True"):
        assert word in check
    # the program's counters reached the [window] line
    window = [line for line in lines if line.startswith("[window]")][0]
    for cls in ("GETRF", "GESSM", "TSTRF", "SSSSM"):
        assert f"'tasks.{cls}'" in window and f"'launches.{cls}'" in window
    for name in ("int_tiles_staged", "region_merges", "lone_in_place"):
        assert f"'{name}'" in window


def test_the_untraced_rehearsal_prints_the_end_to_end_metrics(capsys,
                                                              checkout):
    last, _lines = _dry_run(capsys, checkout, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert {n + DRY_SUFFIX for n in ("tflops_per_chip", "step_s_p50",
                                     "setup_s")} <= set(last["metrics"])
