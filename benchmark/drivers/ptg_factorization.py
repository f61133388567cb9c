"""Driver: a tiled factorization as a PTG taskpool on the dynamic path.

One step is what a DPLASMA user pays for one ``dpotrf`` through the
runtime's scheduler (``testing_dpotrf -N <n> -t <NB>``, one accelerator):
the taskpool the configuration names (``build_potrf``: the ``zpotrf_L``
classes POTRF, TRSM, SYRK, GEMM with the JDF's dependencies and
priorities) is built over the tiled matrix, ``ctx.add_taskpool`` unfolds
it task by task through the PTG front end, the Context's workers and the
chip's device module, the pool is waited for, and ``block_until_ready``
on the lower tiles, where the classes' own write-backs left the factor.
The Context (``parsec.init(nb_cores=...)``) is started once in set-up.
Nothing here computes any part of the factor.

The collection stores the lower triangle alone, as ``testing_dpotrf``
allocates it (``SymTwoDimBlockCyclic``, lower): tile (i, j), i ≥ j, is a
``jax.Array`` committed to the chip before the step starts. The matrix
is ``dpotrf_panel``'s for the same seed: block row j of
``generate.spd_row`` cut into tiles gives, transposed, block column j of
the lower triangle.

The next matrix is written over the last factor, tile by tile, one block
column in flight, before the step starts: a step runs in the matrix's own
storage, as upstream's does, and that is a guarantee of the configuration
(``storage`` in its file). The chip's allocator keeps one peak for the
life of the process, so it is held to it twice: when the warm step ends,
where a program that holds the updated tiles beside the matrix raises
(it cannot run this deployment, and the run ends there), and over the
whole window in ``check``, as a part of ``correct``.
"""

from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp

from benchmark import generate, ops

WAIT_LIMIT_S = 600.0        # a pool that has not ended by then never will


class PtgFactorization:
    def __init__(self, config, sizes, seed, devices, spans, reference):
        self.config, self.seed, self.devices = config, seed, devices
        self.spans, self.ref = spans, reference
        self.n, self.nb = int(sizes["n"]), int(sizes["nb"])
        if self.n % self.nb:
            raise ValueError(f"n={self.n} is not a multiple of "
                             f"nb={self.nb}")
        nt = self.nt = self.n // self.nb
        self.ops_per_step = getattr(ops, config["ops"])(self.n)
        self.bytes_per_step = getattr(ops, config["min_bytes"])(self.n, 4)
        # POTRF(k), TRSM(m,k), SYRK(m,k), GEMM(m,n,k) of zpotrf_L
        self.tasks_per_step = (nt + nt * (nt - 1)
                               + nt * (nt - 1) * (nt - 2) // 6)
        self.lower = [(i, j) for j in range(nt) for i in range(j, nt)]
        itemsize = jnp.dtype(sizes["dtype"]).itemsize
        self.storage_limit_bytes = \
            config["storage"]["peak_over_stored_limit"] * \
            len(self.lower) * self.nb * self.nb * itemsize
        self.steps_run = 0
        # the program's own counters over the window, for the readers:
        # the harness keeps what setup() returns in the record it hands
        # them, and this dict is filled when the window has closed
        self.window_counters = {}
        self._counters_before = None
        self.ctx = self.A = None

    def _tiles(self):
        """The factor's tiles."""
        return [self.A.data_of(key) for key in self.lower]

    # -- set-up: the Context, the collection, the generator ---------------
    def setup(self):
        import parsec_tpu as parsec
        from parsec_tpu.data.matrix import SymTwoDimBlockCyclic, TiledMatrix
        from parsec_tpu.utils import mca_param

        for knob, value in self.config["knobs"].items():
            mca_param.set(knob, value)
        # one device module per chip of the cell, whatever else is visible
        mca_param.set("device.tpu.max_devices", len(self.devices))
        mod, _, fn = self.config["taskpool"].partition(":")
        self._build = getattr(importlib.import_module(mod), fn)
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
            # device module: beside a real accelerator the registry
            # weights the inline CPU module out, and so does this
            for d in self.ctx.devices.devices:
                if d.name == "cpu":
                    d.weight = 0.01
        self.A = TiledMatrix(n, n, nb, nb, name="A",
                             dist=SymTwoDimBlockCyclic(1, 1, uplo="lower"))

        def column(key, j):
            """Block row ``j`` of D (``j`` traced: one program),
            transposed and cut into the tiles ``(c, j)`` of A0's block
            column ``j``; those with ``c < j`` are not stored, and the
            caller drops them."""
            row = generate.spd_row(key, j, n, nb)
            tiles = []
            for c in range(nt):
                t = row[:, c * nb:(c + 1) * nb]
                tiles.append(jnp.where(c == j, 0.5 * (t + t.T), t.T))
            return tiles

        # committed to the chip, as a tile a task made is: a jitted
        # program compiles once more for every pattern of committed and
        # uncommitted arguments it meets, and would meet new ones inside
        # the window
        self._column = jax.jit(
            column, out_shardings=jax.sharding.SingleDeviceSharding(
                self.devices[0]))
        return {"context_s": time.perf_counter() - t0,
                "program_counters": self.window_counters}

    # -- one step ---------------------------------------------------------
    def generate(self, step: int, recycle=None):
        """The matrix of step ``step`` over the last factor, in a fixed
        order, one block column in flight."""
        del recycle                     # the collection itself
        # every step starts from the same collector state, as in the
        # dtd_gemm driver: the last step's pool is cyclic garbage
        gc.collect()
        key = generate.step_key(self.seed, step)
        for j in range(self.nt):
            tiles = jax.block_until_ready(self._column(key, j))
            for c in range(j, self.nt):
                self.A.write_tile((c, j), tiles[c])
            del tiles
        return self.A

    def _peak_bytes(self) -> int:
        """The most the chip has held since the process started (0
        where the platform keeps no such count: a CPU rehearsal)."""
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)

    def step(self, A):
        with self.spans.span("submit"):
            # one name for every factorization ("potrf"): the Context
            # keeps terminated pools by name
            tp = self._build(A)
            self.ctx.add_taskpool(tp)
        with self.spans.span("wait"):
            if not tp.wait_completed(WAIT_LIMIT_S):
                raise RuntimeError(
                    f"the pool had not ended after {WAIT_LIMIT_S} s")
            jax.block_until_ready(self._tiles())
        self.steps_run += 1
        if self.steps_run == 1 and \
                self._peak_bytes() > self.storage_limit_bytes:
            raise RuntimeError(
                f"the warm step held {self._peak_bytes()} bytes on the "
                f"chip, over the configuration's storage guarantee of "
                f"{self.storage_limit_bytes:.0f}: this program does not "
                f"factor in the matrix's own storage")
        return A

    def finite(self, A) -> bool:
        return bool(_all_finite(self._tiles()))

    def counters(self):
        """Tasks each device module ran since the Context started. What
        else the program counts while its stage timers are on (tasks and
        launches by task class on the chip's modules, why group takes
        ended) is kept from the first reading, when the window opens, to
        the second, and their difference left in ``window_counters``. A
        program without those counters leaves nothing there."""
        more = {}
        stats = self.ctx.devices.dump_statistics()
        for s in stats:
            if not s["name"].startswith("tpu"):
                continue
            for what in ("tasks", "launches"):
                for cls, n in s.get(what + "_by_class", {}).items():
                    key = f"{what}.{cls}"
                    more[key] = more.get(key, 0) + n
        for es in self.ctx.streams:
            for name, n in es.stats.items():
                if name.startswith("group_end_"):
                    more[name] = more.get(name, 0) + n
        if self._counters_before is None:
            self._counters_before = more
        else:
            self.window_counters.clear()
            self.window_counters.update(
                {name: n - self._counters_before.get(name, 0)
                 for name, n in more.items()})
        return {"tasks_by_module": {s["name"]: s["tasks"] for s in stats},
                "program_counters": more}

    # -- outside the window -----------------------------------------------
    def check(self, A, step: int):
        """Residual of the factor the timed step left in the collection
        against A0 rebuilt from the seed, a block row of the input and a
        tile of the factor at a time; and that every task of every step
        ran on the chip's module."""
        n, nb = self.n, self.nb
        key = generate.step_key(self.seed, step)
        with jax.default_matmul_precision("highest"):
            row = jax.jit(lambda j, key, x, y: self.ref.probe_input_row(
                j, key, x, y, n=n, nb=nb))
            factor_t = jax.jit(self.ref.probe_factor_t)
            factor = jax.jit(self.ref.probe_factor)
            with jax.default_device(self.devices[0]):
                x = self.ref.probe_vectors(key, n)
                y, z, y2 = (jnp.zeros_like(x) for _ in range(3))
            for j in range(self.nt):
                y = row(j, key, x, y)
            for i, j in self.lower:
                z = factor_t(i, j, A.data_of((i, j)), x, z)
            for i, j in self.lower:
                y2 = factor(i, j, A.data_of((i, j)), z, y2)
            err = self.ref.residual(y, y2)
        limit = self.config["correct"]["limit"]
        on_chip = all(isinstance(t, jax.Array) and
                      t.devices() == {self.devices[0]}
                      for t in self._tiles())
        by_module = {s["name"]: s["tasks"]
                     for s in self.ctx.devices.dump_statistics()}
        tasks_on_chip = sum(n for name, n in by_module.items()
                            if name.startswith("tpu"))
        tasks = self.tasks_per_step * self.steps_run
        peak = self._peak_bytes()
        ok = err == err and err <= limit and on_chip and \
            tasks_on_chip == sum(by_module.values()) == tasks and \
            peak <= self.storage_limit_bytes
        return ok, {"residual": err, "limit": limit,
                    "factor_on_chip": on_chip,
                    "peak_bytes": peak,
                    "storage_limit_bytes": round(self.storage_limit_bytes),
                    "tasks_on_chip": tasks_on_chip,
                    "tasks_of_the_steps": tasks}

    def close(self):
        import parsec_tpu as parsec
        from parsec_tpu.utils import mca_param
        if self.ctx is not None:
            parsec.fini(self.ctx)
        for knob in (*self.config["knobs"], "device.tpu.max_devices"):
            mca_param.unset(knob)


@jax.jit
def _all_finite(tiles):
    return jnp.stack([jnp.isfinite(t).all() for t in tiles]).all()


def build(config, sizes, seed, devices, spans, reference):
    return PtgFactorization(config, sizes, seed, devices, spans, reference)
