"""The ``dgeqrf_ptg_host`` configuration and its cell: what was added to
``BENCHMARK.json`` for them (found by name, not by position), that every
new name resolves, the plain reference against a dense Q and
``numpy.linalg.qr`` at test size, the operation counts, the driver's check
(a wrong tile, a V2 and a T tile rounded to bfloat16, a broken storage
guarantee and another step's matrix all fail it), the new reader on
hand-made events, and the rehearsals, which print every new metric under
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

from benchmark import generate, ops, ops_geqrf  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, Spans, main  # noqa: E402

MAN = Manifest(ROOT)
CELL, CONFIG = "geqrf_ptg_host_n32768_nb2048", "dgeqrf_ptg_host"
REF = MAN.reference("dgeqrf_ptg_host_reference")
READER = MAN.reader("device_seconds_by_program")
# name -> (unit, better, source, layer, moves, reader, params)
NEW = {
    "qr_host_us_per_task": ("us", "lower", "host_clock", "host_runtime",
                            "step_s_p50", "host_us_per_task", None),
    "qr_tasks_per_launch": ("ratio", "higher", "program_span",
                            "device_layer", "step_s_p50",
                            "tasks_per_launch", None),
    "qr_tasks_on_chip_share": ("%", "higher", "program_counter",
                               "device_layer", "tflops_per_chip",
                               "tasks_on_chip_share", None),
    "qr_device_step_s": ("s", "lower", "device_trace", "kernels",
                         "step_s_p50", "device_step_s", None),
    "qr_tile_roofline": ("%", "higher", "device_trace", "kernels",
                         "tflops_per_chip", "roofline", None),
    "qr_tsmqr_roofline": ("%", "higher", "device_trace", "kernels",
                          "tflops_per_chip", "device_seconds_by_program",
                          {"roofline": "TSMQR"}),
    "qr_tsqrt_roofline": ("%", "higher", "device_trace", "kernels",
                          "tflops_per_chip", "device_seconds_by_program",
                          {"roofline": "TSQRT"}),
    "qr_panel_kernel_share": ("%", "lower", "device_trace", "kernels",
                              "step_s_p50", "device_seconds_by_program",
                              {"share": ["GEQRT", "TSQRT"]}),
}
DEVICE_TRACE = {n for n, spec in NEW.items() if spec[2] == "device_trace"}


def _named(section, name):
    (entry,) = [e for e in MAN.bench[section] if e["name"] == name]
    return entry


# -- what BENCHMARK.json gained, and that it resolves ------

def test_the_configuration_the_cell_and_the_metrics_are_found_by_name():
    config = _named("configs", CONFIG)
    assert config == {
        "name": CONFIG, "source": config["source"],
        "file": "benchmark/configs/dgeqrf_ptg_host.json", "reduced": [],
        "why": config["why"]}
    source = config["source"]
    assert "dplasma/blob/master/src/zgeqrf.jdf" in source
    assert "testing_zgeqrf.c" in source and len(source) <= 200
    assert "-N <n> -t <NB> -i <IB>" in source
    assert source == MAN.config(CONFIG)["source"]
    cell = _named("workloads", CELL)
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "n32768_nb2048", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    # one cell of the configuration, one configuration of that name
    assert [w["name"] for w in MAN.bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    for name, (unit, better, source, layer, moves, _r, _p) in NEW.items():
        assert _named("per_layer", name) == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]}
    # no accepted metric's list was extended: the cell reports the metrics
    # that have no list, and its own
    mine = {m["name"] for m in MAN.metrics_for("per_layer", CELL)}
    assert mine == set(NEW) | {"plan_compile_s", "compiles_in_window",
                               "device_idle_share"}
    assert {m["name"] for m in MAN.metrics_for("end_to_end", CELL)} == {
        "tflops_per_chip", "step_s_p50", "peak_hbm_gib", "setup_s"}
    layers = {m["layer"] for m in MAN.bench["per_layer"]
              if m["name"] not in NEW}
    assert {spec[3] for spec in NEW.values()} <= layers


def test_every_new_name_resolves():
    for name, (_u, _b, _s, _l, _m, reader, params) in NEW.items():
        spec = MAN.metric(name)
        assert spec["name"] == name and spec["what"]
        assert (spec["reader"], spec.get("params")) == (reader, params)
        assert callable(MAN.reader(reader).read)
    config = MAN.config(CONFIG)
    assert callable(MAN.driver(config["driver"]).build)
    assert config["reference"] == "dgeqrf_ptg_host_reference"


def test_the_configuration_file_states_the_deployment():
    config = MAN.config(CONFIG)
    workload = MAN.workload(CELL)
    assert workload["dry"] == {"n": 256, "nb": 32, "ib": 16}
    sizes = config["sizes"]
    assert sizes["nb"] == 2048 and sizes["dtype"] == "float32"
    # IB: a key of the configuration, a multiple of 128 that divides NB;
    # the cell restates it (a rehearsal's `dry` sizes may only name what
    # the traffic names)
    assert sizes["ib"] % 128 == 0 and 2048 % sizes["ib"] == 0
    assert workload["traffic"] == {"n": 32768, "nb": 2048,
                                   "ib": sizes["ib"]}
    assert config["reduced"] == [] and config["nb_cores"] == 4
    assert config["taskpool"].endswith(":build_geqrf")
    correct = config["correct"]
    assert 0 < correct["orthogonality_limit"] < correct["norm_limit"] \
        < correct["limit"] < 0.1
    for word in ("bfloat16", "V2", "4 times"):
        assert word in config["correct"]["reason"]
    for word in ("A0 = Q R", "upper triangle", "apply_q", "tpu0", "fresh",
                 "storage of A and T"):
        assert word in config["guarantees"]
    for key in ("nb", "ib", "nb_cores", "precision", "priorities",
                "from_memory"):
        assert config["assumed"][key]
    assert 1.0 < config["storage"]["peak_over_stored_limit"] < 2.0
    # the traffic as ISSUE 33 names it
    d = _driver({"n": 32768, "nb": 2048})
    assert d.tasks_by_class == {"GEQRT": 16, "UNMQR": 120, "TSQRT": 120,
                                "TSMQR": 1240}
    assert d.tasks_per_step == 1496
    assert d.ops_per_step == pytest.approx(46.9e12, rel=2e-3)
    assert 256 * 2048 * 2048 * 4 == 4 << 30
    assert d.stored_bytes == (4 << 30) + 136 * sizes["ib"] * 2048 * 4


def test_the_operation_counts_are_the_kernels_sums():
    """``geqrf_ops`` against the per-kernel counts over the grid: the
    leading terms agree (what is left is the lower-order terms of the
    LAPACK count, under NB/N of it)."""
    for n, nb, ib in ((32768, 2048, 256), (8192, 1024, 1024),
                      (512, 64, 32)):
        nt = n // nb
        tasks = ops_geqrf.geqrf_tasks(nt, nt)
        kernels = ops_geqrf.geqrf_kernels(nb, ib, 4)
        assert set(tasks) == set(kernels) == {"GEQRT", "UNMQR", "TSQRT",
                                              "TSMQR"}
        summed = sum(tasks[c] * kernels[c][0] for c in tasks)
        assert summed == pytest.approx(4.0 * n ** 3 / 3.0, rel=1e-12)
        assert ops_geqrf.geqrf_ops(n, n) == pytest.approx(
            summed, rel=1.5 / n + 1e-9)
        assert ops_geqrf.geqrf_ops(n, n) > summed
        assert ops_geqrf.geqrf_t_tiles(nt, nt) == nt * (nt + 1) // 2
        assert ops_geqrf.geqrf_min_bytes(n, n, nb, ib, 4) == 4 * (
            2 * n * n + nt * (nt + 1) // 2 * ib * nb)
        # a TSMQR reads V2, T, C1, C2 and writes C1, C2
        assert kernels["TSMQR"][1] == 4 * (5 * nb * nb + ib * nb)
    assert ops_geqrf.geqrf_tasks(6, 3) == {"GEQRT": 3, "UNMQR": 3,
                                           "TSQRT": 12, "TSMQR": 14}
    # the cell: compute-bound, 0.238 s at the v5e's peaks
    peaks = MAN.peaks("TPU v5 lite")
    least, bound = ops.roofline_seconds(
        ops_geqrf.geqrf_ops(32768, 32768),
        ops_geqrf.geqrf_min_bytes(32768, 32768, 2048, 256, 4),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    assert bound == "compute" and least == pytest.approx(0.238, rel=3e-3)


# -- the plain reference ------

def _householder(P):
    """Unblocked Householder QR of a panel (rows x b), float64: V (unit
    lower trapezoid), T by LAPACK's larft recurrence, R."""
    rows, b = P.shape
    P, V, T = P.copy(), np.zeros((rows, b)), np.zeros((b, b))
    for c in range(b):
        v = P[c:, c].copy()
        v[0] += np.sign(v[0] or 1.0) * np.linalg.norm(v)
        v /= v[0]
        tau = 2.0 / (v @ v)
        P[c:, c:] -= tau * np.outer(v, v @ P[c:, c:])
        V[c:, c] = v
        T[:c, c] = -tau * T[:c, :c] @ (V[:, :c].T @ V[:, c])
        T[c, c] = tau
    return V, T, np.triu(P[:b])


def _numpy_factored(a0, nb, ib):
    """The factored form by Householder QR in float64, tile by tile, in
    the layout the reference reads (nothing of the program's kernels):
    ``{(i, j): tile}`` of A and of T."""
    mt, nt = a0.shape[0] // nb, a0.shape[1] // nb
    a = {(i, j): a0[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb].copy()
         for i in range(mt) for j in range(nt)}
    t = {}
    for k in range(nt):
        akk = a[k, k]
        t[k, k] = np.zeros((ib, nb))
        for o in range(0, nb, ib):
            V, T, R = _householder(akk[o:, o:o + ib])
            for tile, cols in [(akk, slice(o + ib, nb))] + [
                    (a[k, n], slice(0, nb)) for n in range(k + 1, nt)]:
                tile[o:, cols] -= V @ (T.T @ (V.T @ tile[o:, cols]))
            akk[o:, o:o + ib] = np.tril(V, -1)
            akk[o:o + ib, o:o + ib] += R
            t[k, k][:, o:o + ib] = T
        for m in range(k + 1, mt):
            amk = a[m, k]
            t[m, k] = np.zeros((ib, nb))
            for o in range(0, nb, ib):
                J = slice(o, o + ib)
                V, T, R = _householder(
                    np.vstack([np.triu(akk[J, J]), amk[:, J]]))
                assert np.allclose(V[:ib], np.eye(ib), atol=1e-12)
                V2 = V[ib:]
                for top, bottom, cols in [(akk, amk, slice(o + ib, nb))] + [
                        (a[k, n], a[m, n], slice(0, nb))
                        for n in range(k + 1, nt)]:
                    W = T.T @ (top[J, cols] + V2.T @ bottom[:, cols])
                    top[J, cols] -= W
                    bottom[:, cols] -= V2 @ W
                akk[J, J] = np.tril(akk[J, J], -1) + R
                amk[:, J] = V2
                t[m, k][:, J] = T
    return a, t


@pytest.mark.parametrize("shape,nb,ib", [((96, 96), 32, 32),
                                         ((96, 64), 32, 16)])
def test_the_references_apply_q_against_a_dense_q(shape, nb, ib):
    import jax
    import jax.numpy as jnp
    m, n = shape
    mt, nt = m // nb, n // nb
    a0 = np.random.default_rng(5).uniform(-0.5, 0.5, shape)
    a, t = _numpy_factored(a0, nb, ib)
    a_tile = lambda i, j: jnp.asarray(a[i, j], jnp.float32)  # noqa: E731
    t_tile = lambda i, j: jnp.asarray(t[i, j], jnp.float32)  # noqa: E731
    Q = REF.dense_q(a_tile, t_tile, mt, nt, nb)
    R = np.triu(np.block([[a[i, j] for j in range(nt)]
                          for i in range(mt)]))[:n]
    np.testing.assert_allclose(Q.T @ Q, np.eye(m), atol=2e-6)
    np.testing.assert_allclose(Q[:, :n] @ R, a0, atol=2e-6)
    q_np, r_np = np.linalg.qr(a0)
    signs = np.sign(np.diagonal(r_np)) * np.sign(np.diagonal(R))
    np.testing.assert_allclose(Q[:, :n], q_np * signs[None, :], atol=1e-5)
    # apply_qt is apply_q's transpose, tile by tile
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.random.default_rng(6).standard_normal((m, 8)),
                        jnp.float32)
        np.testing.assert_allclose(
            REF.apply_q(a_tile, t_tile, x, mt, nt), Q @ np.asarray(x),
            atol=1e-5)
        np.testing.assert_allclose(
            REF.apply_qt(a_tile, t_tile, x, mt, nt), Q.T @ np.asarray(x),
            atol=1e-5)
        # R x: what a diagonal tile holds under its diagonal is not read
        rx = jnp.zeros((m, 8), jnp.float32)
        for i in range(nt):
            for j in range(i, nt):
                rx = REF.probe_r(i, j, a_tile(i, j), x, rx)
        np.testing.assert_allclose(np.asarray(rx)[:n],
                                   R @ np.asarray(x)[:n], atol=1e-5)


def test_the_input_is_rebuilt_a_block_row_at_a_time():
    import jax.numpy as jnp
    nt, nb = 3, 16
    key = generate.step_key(5, 2)
    a0 = REF.dense_a0(key, nt, nt, nb)
    assert a0.dtype == np.float64 and a0.shape == (48, 48)
    assert -0.5 <= a0.min() < a0.max() < 0.5
    for i in range(nt):
        for j in range(nt):
            assert np.array_equal(
                a0[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                np.asarray(generate.tile(key, i * nt + j, nb), np.float64))
    x = REF.probe_vectors(key, nt * nb)
    y = jnp.zeros_like(x)
    for i in range(nt):
        y = REF.probe_input_row(i, key, x, y, nt=nt, nb=nb)
    np.testing.assert_allclose(y, a0 @ np.asarray(x), rtol=1e-4, atol=1e-4)


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
        assert set(facts["kernels"]) == {"GEQRT", "UNMQR", "TSQRT", "TSMQR"}
        assert facts["kernels"]["TSMQR"][0] == 14
        a = d.generate(2)
        a0 = REF.dense_a0(generate.step_key(5, 2), 4, 4, nb)
        for i, j in a.keys():
            t = a.data_of((i, j))
            assert t.committed
            assert np.array_equal(np.asarray(t), a0[
                i * nb:(i + 1) * nb, j * nb:(j + 1) * nb].astype(np.float32))
        before = d.counters()["tasks_by_module"]
        a = d.step(a)
        after = d.counters()["tasks_by_module"]
        assert d.tasks_per_step == 4 + 6 + 6 + 14
        assert after["tpu0"] - before["tpu0"] == 30 == sum(after.values())
        assert d.ops_per_step == ops_geqrf.geqrf_ops(n, n)
        assert d.bytes_per_step == ops_geqrf.geqrf_min_bytes(n, n, nb, ib, 4)
        got = d.readings(a, 2)
        assert set(got) == {"norm", "residual", "orthogonality"}
        assert max(got.values()) < 5e-6
        ok, detail = d.check(a, 2)
        assert ok and detail["factored_form_on_chip"]
        # against numpy: R up to row signs
        R = np.triu(a.to_array().astype(np.float64))
        r_np = np.linalg.qr(a0)[1]
        signs = np.sign(np.diagonal(r_np)) * np.sign(np.diagonal(R))
        np.testing.assert_allclose(R, signs[:, None] * r_np, atol=1e-4)
        # held to limits near what the chip reads, the check tells a
        # tile below the stated precision from the result
        d.config = dict(d.config, correct={
            "limit": 1e-5, "norm_limit": 1e-5, "orthogonality_limit": 1e-5})
        assert d.check(a, 2)[0]
        # the check is of THIS step's input: another step's key fails it
        assert not d.check(a, 3)[0]
        for coll, key in ((a, (2, 1)), (d.T, (2, 1)), (d.T, (1, 1))):
            good = coll.data_of(key)
            coll.write_tile(key, bf16(good))
            ok, detail = d.check(a, 2)
            assert not ok
            assert detail["orthogonality"] > detail["orthogonality_limit"]
            coll.write_tile(key, good)
            assert d.check(a, 2)[0]
        good = a.data_of((1, 2))                # a tile of R
        a.write_tile((1, 2), good + 0.5)
        ok, detail = d.check(a, 2)
        assert not ok and detail["norm"] > detail["norm_limit"]
        assert detail["orthogonality"] <= detail["orthogonality_limit"]
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
    stored = 4 * (128 * 128 + 10 * 16 * 32)
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


def test_a_tree_without_the_factored_form_stops_before_a_context_starts(
        monkeypatch):
    """The parent's ``geqrf.py`` has no ``geqrf_t_collection``: set-up
    raises at once, and ``close`` has no Context to stop."""
    import parsec_tpu.algorithms.geqrf as geqrf
    monkeypatch.delattr(geqrf, "geqrf_t_collection")
    d = _driver({"n": 128, "nb": 32, "ib": 16})
    with pytest.raises(AttributeError):
        d.setup()
    assert d.ctx is None
    d.close()


# -- the new reader ------

def _bench(steps=((10.0, 12.0), (12.5, 14.5)), traced=(0.0, 100.0)):
    return sorted([("traced", *traced)] + [("step", *s) for s in steps],
                  key=lambda s: s[1])


def test_device_seconds_are_summed_by_the_class_a_program_is_named_for():
    modules = {0: [
        ("jit_parsec_TSMQR_x4(123)", 10.0, 10.4),
        ("jit_parsec_TSMQR_x1(77)", 10.5, 10.6),
        ("jit_parsec_TSMQR_x4(123)", 11.9, 12.2),      # cut at the step's end
        ("jit_parsec_TSQRT_x1(5)", 13.0, 13.2),
        ("jit_parsec_GEQRT_x1(6)", 20.0, 21.0),        # in no step
        ("jit_parsec_my_body_x8(9)", 13.5, 13.6),
        ("jit_column(4)", 10.0, 14.0),                  # not a launch
        ("jit__lambda_(2)", 10.0, 14.0)]}               # the parent's name
    seconds, steps = READER.seconds_by_class(modules, _bench())
    assert steps == 2
    assert seconds == pytest.approx({"TSMQR": 0.6, "TSQRT": 0.2,
                                     "my_body": 0.1})
    # mean over chips
    both = READER.seconds_by_class(
        {0: modules[0], 1: [("jit_parsec_TSMQR_x4(1)", 10.0, 10.2)]},
        _bench())[0]
    assert both["TSMQR"] == pytest.approx(0.4)
    kernels = {"TSMQR": [10, 4.0 * 2048 ** 3, 88e6],
               "TSQRT": [2, 2.0 * 2048 ** 3, 70e6],
               "GEQRT": [1, 1e9, 1e6]}
    peaks = MAN.peaks("TPU v5 lite")
    shares = READER.rooflines(seconds, steps, kernels, peaks)
    least = 4.0 * 2048 ** 3 / 197e12
    assert set(shares) == {"TSMQR", "TSQRT"}
    assert shares["TSMQR"] == pytest.approx(100 * 20 * least / 0.6)


@pytest.mark.parametrize("modules,bench", [
    ({}, _bench()),                                         # no device plane
    ({0: [("jit__lambda_(2)", 10.0, 11.0)]}, _bench()),     # the parent
    ({0: [("jit_parsec_TSMQR_x4(1)", 10.0, 11.0)]}, _bench(steps=())),
    ({0: [("jit_parsec_TSMQR_x4(1)", 10.0, 11.0)]},
     [("step", 10.0, 12.0)])])                              # not traced
def test_a_trace_without_named_programs_reads_none(modules, bench):
    assert READER.seconds_by_class(modules, bench) is None


def test_without_a_trace_the_reader_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(READER, "_CHECKOUT", str(tmp_path))
    record = {"cell": CELL, "trace": {}, "setup": {}, "peaks": None}
    for spec in ("qr_tsmqr_roofline", "qr_panel_kernel_share"):
        assert READER.read(record, MAN.metric(spec)["params"]) is None


def test_the_chip_module_names_a_launchs_program_for_its_task_class():
    """The name a device trace keeps of a launch: ``device/tpu.py``
    builds every program under ``parsec_<class>_x<tasks>``, and jit puts
    ``jit_`` before it."""
    import parsec_tpu as parsec
    from parsec_tpu.algorithms.geqrf import build_geqrf
    from parsec_tpu.data import TiledMatrix
    ctx = parsec.init(nb_cores=1)
    ctx.start()
    try:
        dev = next(d for d in ctx.devices.devices
                   if d.name.startswith("tpu"))
        for d in ctx.devices.devices:
            if d.name == "cpu":
                d.weight = 0.01
        A = TiledMatrix.from_array(np.random.default_rng(0).uniform(
            -0.5, 0.5, (64, 64)).astype(np.float32), 16, 16, name="A")
        ctx.add_taskpool(build_geqrf(A, ib=8))
        assert ctx.wait(timeout=120)
        names = set()
        for record in dev._table.values():
            for slot, programs in record.items():
                if isinstance(programs, dict):
                    names |= {fn.__name__ for fn in programs.values()}
        assert {"parsec_GEQRT_x1", "parsec_TSQRT_x1", "parsec_UNMQR_x1",
                "parsec_TSMQR_x1"} <= names
        for name in names:
            assert READER.PROGRAM.match("jit_" + name + "(1234)")
    finally:
        parsec.fini(ctx)


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
    rc = main(["--workload", CELL, "--seed", "3300000019", "--seconds",
               "1.0", "--trace", str(trace), "--dry-run-cpu=1"], root=root)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


def test_the_traced_rehearsal_prints_every_new_metric(capsys, checkout):
    last, lines = _dry_run(capsys, checkout, 1)
    assert last["correct"] is True and last["failed"] == 0
    got = {n[:-len(DRY_SUFFIX)]: m for n, m in last["metrics"].items()}
    # all but those that read the device's plane of the trace
    assert set(NEW) - DEVICE_TRACE <= set(got)
    assert not DEVICE_TRACE & set(got)
    for name in set(NEW) - DEVICE_TRACE:
        assert got[name]["unit"] == NEW[name][0] and got[name]["value"] >= 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["qr_tasks_on_chip_share"]["value"] == 100.0
    assert 1.0 <= got["qr_tasks_per_launch"]["value"] <= 204
    check = [line for line in lines if line.startswith("[check]")][0]
    for word in ("norm=", "residual=", "orthogonality=",
                 "factored_form_on_chip=True"):
        assert word in check
    # the program's counters reached the [window] line by class
    window = [line for line in lines if line.startswith("[window]")][0]
    for cls in ("GEQRT", "UNMQR", "TSQRT", "TSMQR"):
        assert f"'tasks.{cls}'" in window and f"'launches.{cls}'" in window


def test_the_untraced_rehearsal_prints_the_end_to_end_metrics(capsys,
                                                              checkout):
    last, _lines = _dry_run(capsys, checkout, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {
        n + DRY_SUFFIX for n in ("tflops_per_chip", "step_s_p50", "setup_s")}
