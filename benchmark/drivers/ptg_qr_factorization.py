"""Driver: a tiled QR factorization as a PTG taskpool on the dynamic path.

One step is what a DPLASMA user pays for one ``dgeqrf`` through the
runtime's scheduler (``testing_dgeqrf -N <n> -t <NB> -i <IB>``, M = N,
one accelerator): the taskpool the configuration names (``build_geqrf``:
zgeqrf.jdf's classes GEQRT, UNMQR, TSQRT, TSMQR over descA and descT) is
built over the tiled matrix and its T, ``ctx.add_taskpool`` unfolds it
task by task through the PTG front end, the Context's workers and the
chip's device module, the pool is waited for, and ``block_until_ready``
on every tile of A and of T, where the classes' own write-backs left the
factored form: R in the upper triangle of A, V under it, the T factors
beside it. The Context (``parsec.init(nb_cores=...)``) is started once in
set-up. Nothing here computes any part of the factorization.

A is a full square collection of nb × nb tiles, T one of ib × nb tiles
of which those on and under the diagonal are written; every tile is a
``jax.Array`` committed to the chip. The matrix is made as
``dplasma_dplrnt`` makes it (uniform in [-0.5, 0.5)), tile (i, j) from
``generate.tile`` with index ``i*nt + j``.

The next matrix is written over the last factored form, tile by tile,
one block column in flight, before the step starts: a step runs in the
storage of A and T, as upstream's does, and that is a guarantee of the
configuration (``storage`` in its file), held as ``ptg_factorization``
holds the POTRF configurations': when the warm step ends, where a
program that holds the updated tiles beside the matrix raises, and over
the whole window in ``check``, as a part of ``correct``. The step, the
counters and the tear-down are ``ptg_factorization``'s, which this driver
extends.
"""

from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp

from benchmark import generate, ops_geqrf
from benchmark.drivers.ptg_factorization import PtgFactorization


class PtgQrFactorization(PtgFactorization):
    def __init__(self, config, sizes, seed, devices, spans, reference):
        # the POTRF driver's fields, for a full square A and its T
        self.config, self.seed, self.devices = config, seed, devices
        self.spans, self.ref = spans, reference
        self.n, self.nb = int(sizes["n"]), int(sizes["nb"])
        self.ib = int(sizes["ib"])
        if self.n % self.nb or self.nb % self.ib:
            raise ValueError(f"n={self.n}, nb={self.nb}, ib={self.ib}: "
                             f"each has to divide the one before")
        nt = self.nt = self.n // self.nb
        self.itemsize = jnp.dtype(sizes["dtype"]).itemsize
        self.ops_per_step = ops_geqrf.geqrf_ops(self.n, self.n)
        self.bytes_per_step = ops_geqrf.geqrf_min_bytes(
            self.n, self.n, self.nb, self.ib, self.itemsize)
        self.tasks_by_class = ops_geqrf.geqrf_tasks(nt, nt)
        self.tasks_per_step = sum(self.tasks_by_class.values())
        self.t_keys = [(m, k) for k in range(nt) for m in range(k, nt)]
        self.stored_bytes = self.itemsize * self.nb * (
            nt * nt * self.nb + len(self.t_keys) * self.ib)
        self.storage_limit_bytes = \
            config["storage"]["peak_over_stored_limit"] * self.stored_bytes
        self.steps_run = 0
        self.window_counters = {}
        self._counters_before = None
        self.ctx = self.A = self.T = None

    def _tiles(self):
        """The factored form's tiles: all of A, and what is written of T."""
        return [self.A.data_of(key) for key in self.A.keys()] + \
            [self.T.data_of(key) for key in self.t_keys]

    # -- set-up: the Context, the collections, the generator --------------
    def setup(self):
        import parsec_tpu as parsec
        from parsec_tpu.data.matrix import TiledMatrix
        from parsec_tpu.utils import mca_param

        # a tree whose dgeqrf keeps no V and no T cannot run this
        # deployment, and says so before a Context starts
        mod, _, fn = self.config["taskpool"].partition(":")
        module = importlib.import_module(mod)
        build = getattr(module, fn)
        t_collection = getattr(module, self.config["t_collection"])
        self._build = lambda A: build(A, self.T)
        for knob, value in self.config["knobs"].items():
            mca_param.set(knob, value)
        # one device module per chip of the cell, whatever else is visible
        mca_param.set("device.tpu.max_devices", len(self.devices))
        n, nb, nt = self.n, self.nb, self.nt
        t0 = time.perf_counter()
        self.ctx = parsec.init(nb_cores=int(self.config["nb_cores"]))
        self.ctx.start()
        mods = [d for d in self.ctx.devices.devices
                if d.name.startswith("tpu")]
        want = self.devices[0].platform
        if len(mods) != len(self.devices) or \
                any(m.platform != want for m in mods):
            raise RuntimeError(
                f"device modules {[(m.name, m.platform) for m in mods]} "
                f"for {len(self.devices)} {want} chips")
        if want == "cpu":
            # a rehearsal takes the chip's path, every body through the
            # device module (ptg_factorization's rule)
            for d in self.ctx.devices.devices:
                if d.name == "cpu":
                    d.weight = 0.01
        self.A = TiledMatrix(n, n, nb, nb, name="A")
        self.T = t_collection(self.A, self.ib)

        def column(key, j):
            """The tiles (c, j) of A0's block column ``j`` (``j`` traced:
            one program)."""
            return [generate.tile(key, c * nt + j, nb) for c in range(nt)]

        # committed to the chip, as a tile a task made is
        self._column = jax.jit(
            column, out_shardings=jax.sharding.SingleDeviceSharding(
                self.devices[0]))
        kernels = ops_geqrf.geqrf_kernels(nb, self.ib, self.itemsize)
        return {"context_s": time.perf_counter() - t0,
                "program_counters": self.window_counters,
                # per class: tasks a step, operations and least bytes a
                # task (device_seconds_by_program divides by these)
                "kernels": {cls: [self.tasks_by_class[cls], *kernels[cls]]
                            for cls in kernels}}

    # -- the next matrix --------------------------------------------------
    def generate(self, step: int, recycle=None):
        """The matrix of step ``step`` over the last factored form, in a
        fixed order, one block column in flight."""
        del recycle                     # the collection itself
        gc.collect()                    # as ptg_factorization
        key = generate.step_key(self.seed, step)
        for j in range(self.nt):
            tiles = jax.block_until_ready(self._column(key, j))
            for c in range(self.nt):
                self.A.write_tile((c, j), tiles[c])
            del tiles
        return self.A

    # -- outside the window -----------------------------------------------
    def readings(self, A, step: int):
        """The three residuals of the factored form the timed step left
        in A and T against A0 rebuilt from the seed, for 8 probe
        vectors x, every product at highest precision:
        ``norm`` |‖R x‖ − ‖A0 x‖| / ‖A0 x‖ (orthogonal invariance: no V,
        no T), ``residual`` ‖A0 x − Q (R x)‖ / ‖A0 x‖, ``orthogonality``
        ‖Qᵀ (Q x) − x‖ / ‖x‖."""
        ref, nt, nb = self.ref, self.nt, self.nb
        key = generate.step_key(self.seed, step)
        a, t = (lambda i, j: A.data_of((i, j))), \
            (lambda i, j: self.T.data_of((i, j)))
        with jax.default_matmul_precision("highest"):
            row = jax.jit(lambda i, key, x, y: ref.probe_input_row(
                i, key, x, y, nt=nt, nb=nb))
            r_tile = jax.jit(ref.probe_r)
            with jax.default_device(self.devices[0]):
                x = ref.probe_vectors(key, self.n)
                y, rx = jnp.zeros_like(x), jnp.zeros_like(x)
            for i in range(nt):
                y = row(i, key, x, y)
            for i in range(nt):
                for j in range(i, nt):
                    rx = r_tile(i, j, a(i, j), x, rx)
            qrx = ref.apply_q(a, t, rx, nt, nt)
            qtqx = ref.apply_qt(a, t, ref.apply_q(a, t, x, nt, nt), nt, nt)
            return {"norm": abs(ref.norm(rx) - ref.norm(y)) / ref.norm(y),
                    "residual": ref.norm(y - qrx) / ref.norm(y),
                    "orthogonality": ref.norm(qtqx - x) / ref.norm(x)}

    def check(self, A, step: int):
        """The three readings under their limits; every tile of A and T
        on the chip; every task of every step counted once on the chip's
        module; the storage guarantee."""
        got = self.readings(A, step)
        correct = self.config["correct"]
        limits = {"norm": correct["norm_limit"], "residual": correct["limit"],
                  "orthogonality": correct["orthogonality_limit"]}
        on_chip = all(isinstance(t, jax.Array) and
                      t.devices() == {self.devices[0]}
                      for t in self._tiles())
        by_module = {s["name"]: s["tasks"]
                     for s in self.ctx.devices.dump_statistics()}
        tasks_on_chip = sum(n for name, n in by_module.items()
                            if name.startswith("tpu"))
        tasks = self.tasks_per_step * self.steps_run
        peak = self._peak_bytes()
        ok = all(got[k] == got[k] and got[k] <= limits[k] for k in limits) \
            and on_chip and \
            tasks_on_chip == sum(by_module.values()) == tasks and \
            peak <= self.storage_limit_bytes
        detail = {}
        for k in limits:
            detail[k], detail[k + "_limit"] = got[k], limits[k]
        detail.update(factored_form_on_chip=on_chip, peak_bytes=peak,
                      storage_limit_bytes=round(self.storage_limit_bytes),
                      tasks_on_chip=tasks_on_chip, tasks_of_the_steps=tasks)
        return ok, detail


def build(config, sizes, seed, devices, spans, reference):
    return PtgQrFactorization(config, sizes, seed, devices, spans, reference)
