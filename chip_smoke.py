#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that parsec_tpu still starts on the chip.

ONE process drives the runtime's three device paths once, through the entry
points a user calls, at full width, cheapest phase first:

  store     the persistent executor store round-trips a one-device program
            (on a many-chip host too) and a mesh program over every chip
  flash     ring_attention(impl="flash") + FFN step, S=16384/H=4/dh=128/F=2048,
            against impl="xla"; the Pallas kernel must be compiled by Mosaic
  block     build_transformer_block's body_tpu through the host runtime
            (tile_s=1024, dh=128) against reference_block
  host      parsec.init -> Context: DTD GEMM n=2048/nb=512 (insert_gemm_dtd)
            against numpy, DTD POTRF n=4096/nb=512 (insert_potrf_dtd) and
            PTG build_potrf of the same size via add_taskpool/wait;
            every task on a tpuN module, none on the inline CPU module;
            with several chips the POTRF tiles are advised 2D over the
            modules and each POTRF line says, per module, the tasks it
            ran and the bytes copied to it from other chips: every task
            on the module its written tile is advised to
  qr_host   the benchmark's dgeqrf_ptg_host driver once at N=8192 in
            2048-tiles (30 tasks of the four compact-WY kernels through
            add_taskpool/wait): the three residuals of V, T and R against
            the plain reference, every task on the tpu0 module
  lu_host   the benchmark's dgetrf_incpiv_ptg_host driver at N=8192 in
            2048-tiles, once for each inner block IB of 128, 256, 512 (the
            sweep the configuration's IB was fixed by; LU_HOST_N=32768
            runs it at the cell's size): per IB the time of a second step
            and the readings of the factored form against the plain
            reference, pivots on the chip, every task on the tpu0 module
  lu_panel  a block's factorization alone, on no cell's path: the VMEM
            panel kernel (ops/tile_kernels.py _lu_panel) and XLA's
            lax.linalg.lu, each jitted by itself, on float32 stacks of
            256, 640, 1152, 2176 rows x 128 columns (TSTRF's) and 4096,
            8192, 17408, 32768 (dgetrf_1d's panels: the tall kernel over
            4096, at 32768 rows with zero rows under the stack)
            and XLA's on 2176 x 32 and 64: device microseconds a
            call and a pivot step from a trace, the same interchanges
            from both, and the gate the kernel was sent under (under
            0.75 x XLA's time at 2176 x 128 and at every tall height)
  panels    GEMM, GEQRF, GETRF panel programs at NB=1024, N=8192, residuals
  flagship  build_potrf_left -> plan_taskpool -> PanelExecutor, N=40960,
            NB=1024, potrf.trsm_hook=gemm, input generated on device, three
            passes, random-probe residual <= 1e-4, peak HBM printed

and, when more than one chip is visible, the multi-chip checks:

  ring      ring_attention(impl="flash", causal=True) over a `seq` mesh of
            every chip, against the XLA fold
  ici       measure_ici_latency: payload resident on two different chips,
            wire bytes << payload
  sharded   run_sharded on the wavefront executor; the flagship PanelExecutor
            state sharded P("rows") over the mesh at N=40960: one shard per
            chip, residual <= 1e-4, per-chip peak HBM printed

serving/decode.py computes every step in host numpy on a d_model=32 toy: it has
no device path, so there is nothing of it to smoke here (ROADMAP R1 ports it).

The chip is REQUIRED: with no TPU the script exits non-zero, names the device
it did not find, and prints no result. `--dry-run-cpu[=N]` states a CPU dry run
instead (tiny sizes, Pallas interpreted, N virtual devices, every line labelled
platform: cpu) — the way to debug this file without a chip. One process per
chip: nothing here starts a child.

Compile cache: through the one resolution in parsec_tpu/utils/compile_cache.py —
JAX_COMPILATION_CACHE_DIR where set, else the fixed <checkout>/.xla_cache. Run
the script twice against one directory and the second run reports fewer
backend compiles and executor-store hits instead of misses.

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
and the exit code is 0 iff every phase passed.
"""

import argparse
import gc
import importlib.metadata
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FULL = dict(dtd=(2048, 512), potrf_host=(4096, 512),
            qr_host=dict(n=8192, nb=2048),
            lu_host=dict(n=8192, nb=2048, ibs=(128, 256, 512)),
            lu_panel=dict(rows=(256, 640, 1152, 2176, 4096, 8192, 17408,
                                32768), widths=(32, 64), gate=2176),
            panels=(8192, 1024), flagship=(40960, 1024),
            flash=dict(S=16384, H=4, dh=128, F=2048),
            block=dict(H=2, T=2, TS=1024, DH=128, F=512),
            wavefront=(2048, 256), mesh_shape=(1024, 1024))
DRY = dict(dtd=(256, 64), potrf_host=(256, 64),
           qr_host=dict(n=128, nb=32, ib=16),
           lu_host=dict(n=128, nb=32, ibs=(16,)),
           lu_panel=dict(rows=(256, 4224), widths=(), gate=256),
           panels=(256, 64), flagship=(256, 64),
           flash=dict(S=256, H=2, dh=16, F=64),
           block=dict(H=2, T=2, TS=64, DH=16, F=64),
           wavefront=(256, 64), mesh_shape=(64, 64))

# f32 matmuls take bf16 MXU passes on the chip unless
# ops.matmul_precision=highest: comparisons against an f32/f64 reference
# of a default-precision result get this relative tolerance. The
# diagonally-dominant factorization residuals are far tighter (1e-4).
BF16_TOL = 3e-2


def say(phase, **kv):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def rel(a, b):
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def tile_devices(tiles):
    """Ids of the devices a collection's tiles sit on."""
    import jax
    return sorted({d.id for t in tiles if isinstance(t, jax.Array)
                   for d in t.devices()})


def require(cond, what):
    if not cond:
        raise AssertionError(what)


# --------------------------------------------------------------------------
# phases — each takes the run's sizes, raises on a failed check
# --------------------------------------------------------------------------

def _mesh_double(x):          # module-level: stable code fingerprint
    return x @ x.T + 1.0


def phase_store(sz, on_chip):
    """Executor store round trip IN this process: compile+save (or load,
    on a warm directory), drop the in-process callables, resolve again —
    that second resolve must be a store hit that RUNS. A one-device
    program (loading it over every device of the host is what broke the
    store) and a mesh program on its mesh. Not here: a one-device
    program compiled for a NON-default chip — libtpu 0.0.34 reloads it
    onto its first chip; no caller persists one, and the store raises
    at load if it ever sees that."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from parsec_tpu.compiled.spmd import compile_with_plan, make_mesh
    from parsec_tpu.utils import compile_cache as cc

    require(cc.executor_store() is not None, "executor store not enabled")
    devs = jax.devices()
    m, n = sz["mesh_shape"]
    x_h = np.arange(m * n, dtype=np.float32).reshape(m, n) / (m * n)
    ref = x_h @ x_h.T + 1.0

    def one_device():
        return cc.cached_jit(
            _mesh_double, key=("smoke_store_1dev", (m, n)),
            example_args=(jax.ShapeDtypeStruct((m, n), jnp.float32),))

    mesh = make_mesh(len(devs), axis="rows")
    sh = NamedSharding(mesh, P("rows"))

    def on_mesh():
        return compile_with_plan(
            _mesh_double, mesh=mesh, in_shardings=(sh,), out_shardings=sh,
            example_args=(jax.ShapeDtypeStruct((m, n), jnp.float32),),
            key=("smoke_store_mesh", (m, n)))

    for name, build, put in (
            ("one_device", one_device, lambda: jnp.asarray(x_h)),
            (f"mesh{len(devs)}", on_mesh, lambda: jax.device_put(x_h, sh))):
        first = np.asarray(build()(put()))
        s0 = cc.cache_stats()
        cc.reset_in_process_cache()          # "a second process"
        again = build()(put())
        s1 = cc.cache_stats()
        require(s1["store_hits"] == s0["store_hits"] + 1,
                f"{name}: second resolve was not a store hit ({s0} -> {s1})")
        require(np.array_equal(first, np.asarray(again)),
                f"{name}: reloaded program disagrees with the compiled one")
        err = rel(first, ref)
        require(err <= BF16_TOL, f"{name}: rel err {err:.2e}")
        say("store", program=name, reload="hit", rel_err=f"{err:.1e}",
            out_devices=sorted(d.id for d in again.devices()))
    require(cc.cache_stats()["store_errors"] == 0, "store load errors")


def _attention_step(sz, n_dev, causal, on_chip, phase):
    """The bench's transformer step (ring attention + FFN) with the
    Pallas kernel against the same step with the XLA fold, on a `seq`
    mesh of ``n_dev`` devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from parsec_tpu.compiled.ring_attention import ring_attention
    from parsec_tpu.compiled.spmd import make_mesh

    import numpy as np
    f = sz["flash"]
    S, H, dh, F = f["S"], f["H"], f["dh"], f["F"]
    mesh = make_mesh(n_dev, axis="seq")
    sh = NamedSharding(mesh, P("seq"))
    rng = np.random.default_rng(0)
    q, k, v = (jax.device_put(
        rng.standard_normal((S, H, dh)).astype(np.float32), sh)
        for _ in range(3))
    W1 = jnp.asarray(rng.standard_normal((H * dh, F)) / 32, jnp.float32)
    W2 = jnp.asarray(rng.standard_normal((F, H * dh)) / 32, jnp.float32)

    def step(q, impl):
        o = ring_attention(q, k, v, mesh, axis="seq", impl=impl,
                           causal=causal)
        x = o.reshape(o.shape[0], -1)
        h = jnp.maximum(x @ W1, 0.0)
        return (x + h @ W2).reshape(q.shape)

    flash = jax.jit(lambda q: step(q, "flash"))
    text = flash.lower(q).as_text()
    mosaic = "tpu_custom_call" in text
    require(mosaic == on_chip,
            "Pallas kernel was " + ("NOT " if on_chip else "") +
            "compiled by Mosaic (tpu_custom_call " +
            ("absent from" if on_chip else "present in") +
            " the lowered step)")
    t0 = time.perf_counter()
    y_f = jax.block_until_ready(flash(q))
    t_f = time.perf_counter() - t0
    y_x = jax.block_until_ready(jax.jit(lambda q: step(q, "xla"))(q))
    require(y_f.shape == q.shape, f"shape {y_f.shape}")
    require(bool(jnp.isfinite(y_f).all()), "non-finite output")
    err = rel(y_f, y_x)
    require(err <= BF16_TOL, f"flash vs xla rel err {err:.2e}")
    say(phase, seq=q.shape[0], heads=q.shape[1], d_head=q.shape[2],
        devices=n_dev, causal=causal,
        kernel="mosaic" if mosaic else "interpreted",
        first_call_s=f"{t_f:.1f}", flash_vs_xla_rel_err=f"{err:.1e}",
        out_devices=sorted(d.id for d in y_f.devices()))


def phase_flash(sz, on_chip):
    _attention_step(sz, 1, False, on_chip, "flash")


def phase_ring(sz, on_chip):
    import jax
    _attention_step(sz, len(jax.devices()), True, on_chip, "ring")


def phase_block(sz, on_chip):
    """The transformer block's TPU incarnation (body_tpu: the Pallas
    kernel per KV tile, merged into the streaming-softmax chain) through
    the host runtime."""
    import jax
    import numpy as np
    import parsec_tpu as parsec
    from parsec_tpu.algorithms.transformer import (build_transformer_block,
                                                   reference_block)
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.ops.flash_attention import flash_attention

    b = sz["block"]
    H, T, TS, DH, F = b["H"], b["T"], b["TS"], b["DH"], b["F"]
    D = H * DH
    rng = np.random.default_rng(1)
    q, k, v = (rng.standard_normal((H, T * TS, DH)).astype(np.float32)
               for _ in range(3))
    Wo = (rng.standard_normal((D, D)) / np.sqrt(D)).astype(np.float32)
    W1 = (rng.standard_normal((D, F)) / np.sqrt(D)).astype(np.float32)
    W2 = (rng.standard_normal((F, D)) / np.sqrt(F)).astype(np.float32)

    def tiles(x):
        return {(h, i): x[h, i * TS:(i + 1) * TS]
                for h in range(H) for i in range(T)}

    Y = LocalCollection("Y", {(i,): None for i in range(T)})
    tp = build_transformer_block(
        LocalCollection("Q", tiles(q)), LocalCollection("K", tiles(k)),
        LocalCollection("V", tiles(v)), Y, H, T, TS, DH, Wo, W1, W2)
    tile = jax.ShapeDtypeStruct((TS, 1, DH), np.float32)
    mosaic = "tpu_custom_call" in jax.jit(
        lambda a, b_, c: flash_attention(a, b_, c, return_lse=True)
    ).lower(tile, tile, tile).as_text()
    require(mosaic == on_chip, f"tile kernel mosaic={mosaic}")
    ctx = parsec.init(nb_cores=4)
    try:
        ctx.start()
        ctx.add_taskpool(tp)
        require(ctx.wait(timeout=600), "transformer block did not finish")
        stats = ctx.devices.dump_statistics()
    finally:
        parsec.fini(ctx)
    got = np.concatenate([np.asarray(Y.data_of((i,))) for i in range(T)])
    err = rel(got, reference_block(q, k, v, Wo, W1, W2))
    require(np.isfinite(got).all(), "non-finite output")
    require(err <= BF16_TOL, f"block vs reference rel err {err:.2e}")
    say("block", tile_s=TS, d_head=DH, heads=H, tiles=T,
        kernel="mosaic" if mosaic else "interpreted",
        rel_err=f"{err:.1e}",
        tasks={s["name"]: s["tasks"] for s in stats if s["tasks"]})


def phase_host(sz, on_chip):
    """The dynamic path every serving/ workload sits on: host scheduler,
    device bodies."""
    import jax
    import numpy as np
    import parsec_tpu as parsec
    from parsec_tpu import _native, dtd
    from parsec_tpu.algorithms import (build_potrf, insert_gemm_dtd,
                                       insert_potrf_dtd)
    from parsec_tpu.data.matrix import (SymTwoDimBlockCyclic, TiledMatrix,
                                        advise_on_devices)

    n_dev = len(jax.devices())
    # several chips: the POTRF matrices' tiles are advised 2D-cyclically
    # over the chips' modules, as testing_dpotrf -g <n> advises them
    grid = (2, n_dev // 2) if n_dev > 1 and n_dev % 2 == 0 else (1, n_dev)
    rng = np.random.default_rng(0)
    ctx = parsec.init(nb_cores=4)
    try:
        ctx.start()
        mods = [d for d in ctx.devices.devices if d.name.startswith("tpu")]
        require(len(mods) == n_dev,
                f"{len(mods)} device modules for {n_dev} devices")
        require(all(m.platform == ("tpu" if on_chip else "cpu")
                    for m in mods),
                f"module platforms {[m.platform for m in mods]}")

        n, nb = sz["dtd"]
        A_h = rng.standard_normal((n, n)).astype(np.float32)
        B_h = rng.standard_normal((n, n)).astype(np.float32)
        A = TiledMatrix.from_array(A_h, nb, nb, name="A")
        B = TiledMatrix.from_array(B_h, nb, nb, name="B")
        C = TiledMatrix.from_array(np.zeros((n, n), np.float32), nb, nb,
                                   name="C")
        tp = dtd.Taskpool("smoke_gemm")
        ctx.add_taskpool(tp)
        t0 = time.perf_counter()
        insert_gemm_dtd(tp, A, B, C)
        tp.wait()
        c_tiles = [C.data_of(key) for key in C.local_keys()]
        jax.block_until_ready(c_tiles)
        t_gemm = time.perf_counter() - t0
        # engine_for returns None when a real accelerator is registered:
        # on the chip every DTD pool runs the Python engine (ROADMAP S1)
        engine = "native" if tp._native is not None else "python"
        out_devs = tile_devices(c_tiles)
        err = rel(C.to_array(), A_h.astype(np.float64) @ B_h)
        require(err <= BF16_TOL, f"DTD GEMM rel err {err:.2e}")
        say("host", dtd_gemm=f"n={n}/nb={nb}", tasks=(n // nb) ** 3,
            engine=engine, native_build=(
                "loaded" if _native.available() else
                f"unavailable ({_native.build_error()})"),
            first_run_s=f"{t_gemm:.1f}", rel_err=f"{err:.1e}",
            output_devices=out_devs)

        # the paths the benchmark's two POTRF cells on the host scheduler
        # run: the lower triangle alone stored, as testing_dpotrf
        # allocates it, every tile on a chip before the pool starts
        n, nb = sz["potrf_host"]
        nt = n // nb
        R = rng.standard_normal((n, n)).astype(np.float32)
        S_h = (0.5 * (R + R.T) + 2.0 * n * np.eye(n)).astype(np.float32)
        lower = [(i, j) for j in range(nt) for i in range(j, nt)]

        def home(mat, key):
            """The chip the tile at ``key`` is advised to."""
            return mods[mat.device_advice(key) % len(mods)].jax_device

        def spd(name):
            mat = advise_on_devices(
                TiledMatrix(n, n, nb, nb, name=name,
                            dist=SymTwoDimBlockCyclic(1, 1, uplo="lower")),
                grid=grid)
            for i, j in lower:
                mat.write_tile((i, j), jax.device_put(
                    S_h[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                    home(mat, (i, j))))
            return mat

        def placement(mat, before):
            """What the modules and the workers counted since ``before``
            (``counted()``): did placement follow the advice, and what
            crossed between chips for it?"""
            now = counted()
            by_module = {
                name: {k: v - before[0][name][k] for k, v in c.items()}
                for name, c in now[0].items()}
            advised, on_advised = (a - b for a, b in zip(now[1], before[1]))
            off = [key for key in lower
                   if mat.data_of(key).devices() != {home(mat, key)}]
            if n_dev > 1:
                require(advised == tasks and on_advised == tasks,
                        f"{on_advised} of {advised} advised tasks (the "
                        f"graph has {tasks}) ran on their tile's module")
                require(not off, f"tiles off their advised chip: {off}")
            return dict(tasks_on_advised=f"{on_advised}/{advised}",
                        by_module=by_module)

        def counted():
            return ({s["name"]: {k: s[k] for k in (
                "tasks", "remote_copies", "remote_bytes_in", "remote_hits")}
                for s in ctx.devices.dump_statistics()
                if s["name"].startswith("tpu")},
                [sum(es.stats[k] for es in ctx.streams)
                 for k in ("tasks_advised", "tasks_on_advised")])

        # by insertion (dpotrf_dtd): the tester's loop, a flush per tile
        D_ = spd("D")
        tasks = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
        before = counted()
        ran = sum(s["tasks"] for s in ctx.devices.dump_statistics()
                  if s["name"].startswith("tpu"))
        tp = dtd.Taskpool("smoke_potrf")
        ctx.add_taskpool(tp)
        t0 = time.perf_counter()
        insert_potrf_dtd(tp, D_)
        require(tp.wait(timeout=900), "DTD POTRF did not finish")
        jax.block_until_ready([D_.data_of(key) for key in lower])
        t_dtd = time.perf_counter() - t0
        L = np.tril(D_.to_array().astype(np.float64))
        err = rel(L @ L.T, S_h)
        require(err <= 1e-3, f"DTD POTRF residual {err:.2e}")
        ran = sum(s["tasks"] for s in ctx.devices.dump_statistics()
                  if s["name"].startswith("tpu")) - ran
        # the native engine (a CPU dry run's) runs bodies off the modules
        require(ran == (tasks if tp._native is None else 0),
                f"DTD POTRF: {ran} of {tasks} tasks on the tpu modules")
        require(tp.tiles.all() == [] and
                tp.tiles.retired == len(lower),
                f"DTD POTRF flushed {tp.tiles.retired} of {len(lower)} "
                f"tiles, {len(tp.tiles.all())} still tracked")
        say("host", dtd_potrf=f"n={n}/nb={nb}", tasks=tasks,
            on_tpu_modules=ran, first_run_s=f"{t_dtd:.1f}",
            residual=f"{err:.1e}", tiles_flushed=tp.tiles.retired,
            output_devices=tile_devices(D_.data_of(key) for key in lower),
            **(placement(D_, before) if tp._native is None else {}))

        # unfolded by the PTG front end (dpotrf_ptg_host); the stage
        # timers on, so that the modules count tasks by class
        P_ = spd("P")
        before = counted()
        timers = ctx.set_stage_timers(True)
        t0 = time.perf_counter()
        ctx.add_taskpool(build_potrf(P_))
        require(ctx.wait(timeout=900), "PTG POTRF did not finish")
        t_potrf = time.perf_counter() - t0
        ctx.set_stage_timers(timers)
        require(sorted(P_._tiles) == sorted(lower),
                "PTG POTRF touched a tile of the upper triangle")
        L = np.tril(P_.to_array().astype(np.float64))
        err = rel(L @ L.T, S_h)
        require(err <= 1e-3, f"PTG POTRF residual {err:.2e}")
        p_devs = tile_devices(P_.data_of(key) for key in lower)
        stats = ctx.devices.dump_statistics()
        by_class = {}
        for s in stats:
            for cls, count in s["tasks_by_class"].items():
                by_class[cls] = by_class.get(cls, 0) + count
        want = {"POTRF": nt, "TRSM": nt * (nt - 1) // 2,
                "SYRK": nt * (nt - 1) // 2,
                "GEMM": nt * (nt - 1) * (nt - 2) // 6}
        require(by_class == want, f"PTG POTRF tasks by class {by_class}, "
                                  f"the graph has {want}")
        say("host", ptg_potrf=f"n={n}/nb={nb}", first_run_s=f"{t_potrf:.1f}",
            residual=f"{err:.1e}", output_devices=p_devs,
            tasks_by_class=by_class, **placement(P_, before))
    finally:
        parsec.fini(ctx)

    by_name = {s["name"]: s["tasks"] for s in stats}
    say("host", tasks_by_module=by_name)
    # on the CPU platform the inline module is a load-balancing peer of
    # the (CPU-backed) device modules by design; next to a real chip it
    # is weighted out and must have run nothing
    require(not on_chip or by_name.get("cpu", 0) == 0,
            f"the inline CPU module ran {by_name.get('cpu')} device bodies")
    idle = [m.name for m in mods if by_name.get(m.name, 0) == 0]
    require(not idle, f"device modules that ran no task: {idle}")
    if n_dev > 1:
        require(len(set(out_devs) | set(p_devs)) > 1,
                f"every output tile lives on chip {out_devs or p_devs}")


def phase_qr_host(sz, on_chip):
    """``dgeqrf`` as the benchmark's cell runs it, small: the driver's own
    set-up, generator, step and check (``benchmark/drivers/
    ptg_qr_factorization.py``), so a builder without the benchmark's
    window sees the path on the chip."""
    import jax
    from benchmark.manifest import Manifest
    from benchmark.run import Spans

    man = Manifest()
    config = man.config("dgeqrf_ptg_host")
    driver = man.driver(config["driver"]).build(
        config, {**config["sizes"], **sz["qr_host"]}, 20261002,
        jax.devices()[:1], Spans(), man.reference(config["reference"]))
    # the storage guarantee is the cell's: at this size the programs'
    # text outweighs the matrix
    driver.storage_limit_bytes = 1 << 62
    try:
        driver.setup()
        t0 = time.perf_counter()
        out = driver.step(driver.generate(0))
        t_step = time.perf_counter() - t0
        require(driver.finite(out), "a non-finite tile")
        ok, detail = driver.check(out, 0)
        say("qr_host", geqrf="n={n}/nb={nb}".format(**sz["qr_host"]),
            ib=driver.ib, tasks=driver.tasks_per_step,
            first_run_s=f"{t_step:.1f}", **detail)
        require(ok, f"the factored form fails its check: {detail}")
    finally:
        driver.close()


def phase_lu_host(sz, on_chip):
    """``dgetrf_incpiv`` as the benchmark's cell runs it, once for each
    inner block of the sweep: the driver's own set-up, generator, step
    and check (``benchmark/drivers/ptg_lu_factorization.py``), a Context
    an IB. The second step's time is the figure (the first compiles)."""
    import jax
    from benchmark.manifest import Manifest
    from benchmark.run import Spans

    man = Manifest()
    config = man.config("dgetrf_incpiv_ptg_host")
    sizes = dict(sz["lu_host"])
    sizes["n"] = int(os.environ.get("LU_HOST_N", sizes["n"]))
    ibs = sizes.pop("ibs")
    if os.environ.get("LU_HOST_IBS"):
        ibs = [int(ib) for ib in os.environ["LU_HOST_IBS"].split(",")]
    for ib in ibs:
        driver = man.driver(config["driver"]).build(
            config, {**config["sizes"], **sizes, "ib": ib}, 20261003,
            jax.devices()[:1], Spans(), man.reference(config["reference"]))
        # the storage guarantee is the cell's: at a small size the
        # programs' text outweighs the matrix
        driver.storage_limit_bytes = 1 << 62
        try:
            driver.setup()
            t0 = time.perf_counter()
            out = driver.step(driver.generate(0))
            t_first = time.perf_counter() - t0
            steps = []
            for step in (1, 2):
                inp = driver.generate(step)
                t0 = time.perf_counter()
                out = driver.step(inp)
                steps.append(time.perf_counter() - t0)
            require(driver.finite(out), "a non-finite tile")
            ok, detail = driver.check(out, 2)
            say("lu_host", getrf_incpiv="n={n}/nb={nb}".format(**sizes),
                ib=ib, tasks=driver.tasks_per_step,
                first_run_s=f"{t_first:.1f}",
                step_s=f"{min(steps):.4f}", **detail)
            require(ok, f"the factored form fails its check: {detail}")
        finally:
            driver.close()
            del driver
            gc.collect()


def _device_us(calls, reps=10):
    """Median device microseconds of one run of each jitted call in
    ``calls`` (``{name: (jitted, argument)}``, the function's name the
    key), from a trace: a program's run is one event, ``jit_<name>``, on
    the chip's ``XLA Modules`` line. ``{}`` where the trace has no
    device plane (a CPU dry run)."""
    import glob
    import re
    import shutil
    import statistics
    import tempfile
    import jax
    from jax.profiler import ProfileData
    trace_dir = tempfile.mkdtemp(prefix="chip_smoke_trace_")
    try:
        jax.profiler.start_trace(trace_dir)
        for fn, arg in calls.values():
            for _ in range(reps):
                out = fn(arg)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for e in line.events:
                    m = re.match(r"jit_(\w+)", e.name)
                    if m and m.group(1) in calls:
                        found.setdefault(m.group(1), []).append(
                            e.duration_ns * 1e-3)
        return {name: statistics.median(us) for name, us in found.items()}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def phase_lu_panel(sz, on_chip):
    """A block's factorization by itself: the VMEM panel kernel against
    XLA's LU on stacks [upper-triangular; dense], the sweep that split
    XLA's time a pivot step into a fixed and a per-row part and the gate
    the kernel was sent under (PERF.md section 6, PRs 41 and 45). Kept
    for the next jax upgrade; no cell runs this phase's programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from parsec_tpu.ops.tile_kernels import (PANEL_COMPILER_OPTIONS,
                                             _lu_panel, _lu_panel_takes,
                                             _lu_panel_tall)

    def xla_lu(stack):
        lu, piv, _ = jax.lax.linalg.lu(stack)
        return lu, piv

    def jitted(fn, name, **options):
        def call(stack):
            return fn(stack)
        call.__name__ = name
        return jax.jit(call, **options)

    # XLA's kernel on a tall stack needs the panel's scoped VMEM
    xla_options = dict(compiler_options=PANEL_COMPILER_OPTIONS) \
        if on_chip else {}
    rng = np.random.default_rng(20261004)
    rows, gate, full = sz["lu_panel"]["rows"], sz["lu_panel"]["gate"], 128
    shapes = [(r, full) for r in rows] + \
        [(gate, w) for w in sz["lu_panel"]["widths"]]
    calls = {}
    for r, w in shapes:
        stack = jnp.asarray(np.concatenate(
            [np.triu(rng.uniform(-0.5, 0.5, (w, w))),
             rng.uniform(-0.5, 0.5, (r - w, w))]).astype(np.float32))
        calls[f"lu_xla_{r}x{w}"] = (
            jitted(xla_lu, f"lu_xla_{r}x{w}", **xla_options), stack)
        if _lu_panel_takes(r, w, stack.dtype):
            calls[f"lu_panel_{r}x{w}"] = (
                jitted(_lu_panel, f"lu_panel_{r}x{w}"), stack)
            lu, piv = calls[f"lu_panel_{r}x{w}"][0](stack)
            want, want_piv = calls[f"lu_xla_{r}x{w}"][0](stack)
            require(bool((piv == want_piv).all()),
                    f"{r}x{w}: the panel's interchanges are not XLA's")
            err = float(jnp.abs(lu - want).max() / jnp.abs(want).max())
            require(err <= 1e-5, f"{r}x{w}: factors {err:.1e} from XLA's")
            require(float(jnp.abs(lu[w:]).max()) <= 1 + 1e-6,
                    f"{r}x{w}: a multiplier over 1")
    for fn, stack in calls.values():
        jax.block_until_ready(fn(stack))
    us = _device_us(calls)

    def shown(value, digits):
        return f"{value:.{digits}f}" if value else "not_measured"

    for r, w in shapes:
        xla, panel = (us.get(f"lu_{k}_{r}x{w}") for k in ("xla", "panel"))
        taken = f"lu_panel_{r}x{w}" in calls
        say("lu_panel", stack=f"{r}x{w}", xla_us=shown(xla, 1),
            xla_us_per_pivot_step=shown(xla and xla / w, 3),
            panel=("tall" if _lu_panel_tall(r) else "packed") if taken
            else "none",
            panel_us=shown(panel, 1) if taken else "none",
            panel_us_per_pivot_step=shown(panel and panel / w, 3)
            if taken else "none",
            panel_over_xla=shown(xla and panel and panel / xla, 3))
    if on_chip:
        for r in [gate] + [r for r in rows if _lu_panel_tall(r)]:
            ratio = us[f"lu_panel_{r}x{full}"] / us[f"lu_xla_{r}x{full}"]
            require(ratio < 0.75, f"the panel takes {ratio:.2f} of XLA's "
                    f"time at {r}x{full}: the gate it was sent under is "
                    "0.75")


def _panel_run(ex, state):
    import jax
    t0 = time.perf_counter()
    fn = ex.jitted                 # shared jit store / executor store
    out = fn(state)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def phase_panels(sz, on_chip):
    """The other three panel programs compile and pass their residuals
    (random-probe identities traced at highest precision)."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.algorithms.gemm import build_gemm_ptg
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    from parsec_tpu.algorithms.getrf import build_getrf_left
    from parsec_tpu.compiled.panels import PanelExecutor
    from parsec_tpu.compiled.wavefront import plan_taskpool
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.ops.tile_kernels import lu_split
    from parsec_tpu.utils import mca_param

    n, nb = sz["panels"]
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.fold_in(key, 99), (n, 8), jnp.float32)

    def tm(name):
        return TiledMatrix(n, n, nb, nb, name=name)

    def rnd(i):
        return jax.random.normal(jax.random.fold_in(key, i), (n, n),
                                 jnp.float32)

    def check(name, ex, state, resid, tol):
        out, t = _panel_run(ex, state)
        with jax.default_matmul_precision("highest"):
            err = float(jax.jit(resid)(out))
        require(err == err and err <= tol, f"{name} residual {err:.2e}")
        say("panels", program=name, n=n, nb=nb, first_call_s=f"{t:.1f}",
            residual=f"{err:.1e}")

    # GEMM: the store holds transposes, so Cᵀ = Bᵀ Aᵀ
    ex = PanelExecutor(plan_taskpool(build_gemm_ptg(tm("A"), tm("B"),
                                                    tm("C"))))
    check("gemm", ex,
          {"A": rnd(0), "B": rnd(1), "C": jnp.zeros((n, n), jnp.float32)},
          lambda o: (jnp.linalg.norm(o["C"] @ x - rnd(1) @ (rnd(0) @ x)) /
                     jnp.linalg.norm(rnd(1) @ (rnd(0) @ x))), BF16_TOL)

    # GEQRF: ‖RᵀRx − AᵀAx‖/‖AᵀAx‖ (orthogonal-invariant identity)
    def resid_qr(o):
        A0t = rnd(2)
        AtAx = A0t @ (A0t.T @ x)
        R = o["A"].T
        return jnp.linalg.norm(R.T @ (R @ x) - AtAx) / jnp.linalg.norm(AtAx)

    check("geqrf", PanelExecutor(plan_taskpool(build_geqrf_hh(tm("A")))),
          {"A": rnd(2)}, resid_qr, BF16_TOL)

    # GETRF (no pivoting, diagonally dominant): ‖LUx − Ax‖/‖Ax‖
    def gen_lu():
        return rnd(3).at[jnp.arange(n), jnp.arange(n)].add(2.0 * n)

    def resid_lu(o):
        L, U = lu_split(o["A"].T)
        Ax = gen_lu().T @ x
        return jnp.linalg.norm(L @ (U @ x) - Ax) / jnp.linalg.norm(Ax)

    mca_param.set("getrf.trsm_hook", "gemm")
    try:
        check("getrf", PanelExecutor(plan_taskpool(build_getrf_left(
            tm("A")))), {"A": gen_lu()}, resid_lu, 1e-4)
    finally:
        mca_param.unset("getrf.trsm_hook")


def _flagship_executor(sz):
    from parsec_tpu.algorithms.potrf import build_potrf_left
    from parsec_tpu.compiled.panels import PanelExecutor
    from parsec_tpu.compiled.wavefront import plan_taskpool
    from parsec_tpu.data.matrix import TiledMatrix
    n, nb = sz["flagship"]
    return PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(n, n, nb, nb, name="A"))))


def _peaks():
    import jax
    out = {}
    for d in jax.devices():
        ms = d.memory_stats() or {}
        if "peak_bytes_in_use" in ms:
            out[d.id] = f"{ms['peak_bytes_in_use'] / 2 ** 30:.2f}GiB"
    return out or "not reported by this backend"


def phase_flagship(sz, on_chip):
    """The compiled path at flagship width: the program of the
    benchmark's ``potrf_panel_n40960`` cell."""
    import jax
    from parsec_tpu.algorithms.potrf import (panel_potrf_residual,
                                             panel_spd_state)
    from parsec_tpu.utils import mca_param

    n, nb = sz["flagship"]
    key = jax.random.PRNGKey(0)
    mca_param.set("potrf.trsm_hook", "gemm")
    try:
        ex = _flagship_executor(sz)
        gen = jax.jit(lambda k: panel_spd_state(k, n, nb))
        out, t_first = _panel_run(ex, gen(key))
        passes = [t_first]
        for _ in range(2):
            del out
            out, t = _panel_run(ex, gen(key))
            passes.append(t)
        with jax.default_matmul_precision("highest"):
            err = float(jax.jit(
                lambda a, k: panel_potrf_residual(a, k, n, nb))(
                    out["A"], key))
    finally:
        mca_param.unset("potrf.trsm_hook")
    say("flagship", n=n, nb=nb, first_pass_s=f"{passes[0]:.1f}",
        later_passes_s=[round(t, 3) for t in passes[1:]],
        residual=f"{err:.2e}", peak_hbm=_peaks())
    require(err == err and err <= 1e-4, f"residual {err:.2e} > 1e-4")


def phase_ici(sz, on_chip):
    """The device hop ONE process can measure: two loopback ranks mapped
    to two chips of one mesh, payload moved chip to chip."""
    from parsec_tpu.comm.pingpong import measure_ici_latency
    r = measure_ici_latency(payload_bytes=1 << 16, hops=32)
    say("ici", **r)
    require(len(r["payload_device_ids"]) == 2,
            f"payload sat on devices {r['payload_device_ids']}")
    require(r["host_bypass"],
            f"{r['wire_bytes_per_hop']} wire bytes per hop for a "
            f"{r['payload_bytes']}-byte payload")


def phase_sharded(sz, on_chip):
    """State sharded over every chip: run_sharded on the wavefront
    executor, then the flagship panel program with its Aᵀ-dense state
    sharded P("rows")."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from parsec_tpu.algorithms.potrf import (build_potrf,
                                             panel_potrf_residual,
                                             panel_spd_state)
    from parsec_tpu.compiled.spmd import (compile_with_plan, make_mesh,
                                          run_sharded)
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.utils import mca_param

    n_dev = len(jax.devices())
    n, nb = sz["wavefront"]
    rng = np.random.default_rng(3)
    R = rng.standard_normal((n, n)).astype(np.float32)
    S_h = (0.5 * (R + R.T) + 2.0 * n * np.eye(n)).astype(np.float32)
    A = TiledMatrix.from_array(S_h.copy(), nb, nb, name="A")
    run_sharded(WavefrontExecutor(plan_taskpool(build_potrf(A))),
                mesh=make_mesh(n_dev, axis="tiles"))
    L = np.tril(A.to_array().astype(np.float64))
    err = rel(L @ L.T, S_h)
    require(err <= 1e-3, f"run_sharded POTRF residual {err:.2e}")
    say("sharded", run_sharded=f"potrf n={n}/nb={nb}", devices=n_dev,
        residual=f"{err:.1e}")

    n, nb = sz["flagship"]
    key = jax.random.PRNGKey(0)
    mesh = make_mesh(n_dev, axis="rows")
    sh = {"A": NamedSharding(mesh, P("rows"))}
    mca_param.set("potrf.trsm_hook", "gemm")
    try:
        ex = _flagship_executor(sz)
        gen = jax.jit(lambda k: panel_spd_state(k, n, nb), out_shardings=sh)
        t0 = time.perf_counter()
        fn = compile_with_plan(
            ex.run_state, mesh=mesh, in_shardings=(sh,), out_shardings=sh,
            donate_argnums=0, example_args=(ex.state_shapes(),),
            fn_key=("smoke_sharded", ex.monolith_cache_key()))
        out = fn(gen(key))
        jax.block_until_ready(out)
        t_first = time.perf_counter() - t0
        shard_devs = sorted(s.device.id for s in out["A"].addressable_shards)
        # who partitioned it: the taskpool's mesh lowering under
        # shard_map (runtime), or the one-chip program handed to GSPMD
        part = ex.partition_report()
        say("sharded", flagship=f"n={n}/nb={nb}", spec='P("rows")',
            branch=part["branch"],
            **{field: part.get(field) for field in (
                "sends_per_step", "sent_bytes_per_step_busiest_chip",
                "send_chunk_bytes", "sends_with_a_product_behind_them_share",
                "collectives_per_step", "reduced_bytes_per_step_and_chip",
                "busiest_chip_ops_share")},
            first_pass_s=f"{t_first:.1f}", shard_devices=shard_devs,
            peak_hbm=_peaks())
        require(part["branch"] == "runtime",
                f"the flagship over a mesh was partitioned by "
                f"{part['branch']}: {part.get('reason')}")
        # the probe's many row-block slices of a row-SHARDED factor make
        # GSPMD plan tens of GB of resharding temporaries (it would not
        # compile): check the factor gathered onto one chip, with the
        # one-chip probe the flagship phase uses
        factor = jax.device_put(out.pop("A"), jax.devices()[0])
        with jax.default_matmul_precision("highest"):
            err = float(jax.jit(
                lambda a, k: panel_potrf_residual(a, k, n, nb))(factor, key))
    finally:
        mca_param.unset("potrf.trsm_hook")
    say("sharded", flagship_residual=f"{err:.2e}")
    require(len(set(shard_devs)) == n_dev,
            f"shards on devices {shard_devs}, want {n_dev} distinct")
    require(err == err and err <= 1e-4, f"residual {err:.2e} > 1e-4")


ONE_CHIP = [("store", phase_store), ("flash", phase_flash),
            ("block", phase_block), ("host", phase_host),
            ("qr_host", phase_qr_host), ("lu_host", phase_lu_host),
            ("lu_panel", phase_lu_panel), ("panels", phase_panels),
            ("flagship", phase_flagship)]
MULTI_CHIP = [("ring", phase_ring), ("ici", phase_ici),
              ("sharded", phase_sharded)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", nargs="?", const=1, type=int,
                    metavar="N", help="CPU dry run on N virtual devices: "
                    "tiny sizes, Pallas interpreted, labelled platform: cpu")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset of phases to run")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax
    want = "tpu"
    if args.dry_run_cpu:
        want = "cpu"
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.dry_run_cpu)
    try:
        devs = jax.devices()
    except RuntimeError as exc:
        sys.exit(f"chip_smoke: no TPU — JAX could not bring the backend "
                 f"up: {exc}")
    found = sorted({d.platform for d in devs})
    if found != [want]:
        sys.exit(f"chip_smoke: no TPU — JAX found only {found} devices. "
                 "Nothing was run. (A CPU dry run must be stated: "
                 "--dry-run-cpu)")
    on_chip = want == "tpu"
    sizes = FULL if on_chip else DRY
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    from parsec_tpu.utils import compile_cache, mca_param
    mca_param.set("jit.cache_dir", "auto")
    cache_dir = compile_cache.enable_compile_cache()
    compile_cache.backend_compile_count()          # install the counter
    import jaxlib
    say("device", platform=device["platform"], device_kind=device["kind"],
        count=device["count"], jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"), cache_dir=cache_dir,
        cache_placed_by=("JAX_COMPILATION_CACHE_DIR"
                         if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                         else "jit.cache_dir=auto"))

    phases = ONE_CHIP + (MULTI_CHIP if len(devs) > 1 else [])
    if args.phases:
        pick = args.phases.split(",")
        unknown = set(pick) - {n for n, _ in ONE_CHIP + MULTI_CHIP}
        if unknown:
            sys.exit(f"chip_smoke: unknown phases {sorted(unknown)}")
        phases = [(n, f) for n, f in ONE_CHIP + MULTI_CHIP if n in pick]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        c0 = compile_cache.backend_compile_count()
        try:
            fn(sizes, on_chip)
            verdict = "PASS"
        except Exception:  # noqa: BLE001 — report, run the rest, exit 1
            traceback.print_exc(file=sys.stdout)
            failed.append(name)
            verdict = "FAIL"
        gc.collect()
        say(name, verdict=verdict, seconds=f"{time.perf_counter() - t0:.1f}",
            backend_compiles=compile_cache.backend_compile_count() - c0)

    stats = compile_cache.cache_stats()
    say("cache", dir=cache_dir,
        backend_compiles=stats["backend_compiles"],
        store_hits=stats["store_hits"], store_misses=stats["store_misses"],
        store_errors=stats["store_errors"],
        total_s=f"{time.perf_counter() - t_start:.1f}")
    say("phases", ran=[n for n, _ in phases], failed=failed)
    result = {"ok": not failed, "device": device}
    if failed:
        result["failed"] = failed
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
