"""Driver: a tiled factorization as ONE PTG taskpool over the chips of a
node, tiles advised 2D-cyclically to the chips' device modules.

One step is what a DPLASMA user pays for one ``dpotrf`` on a node with
several accelerators (``testing_dpotrf -N <n> -t <NB> -g <chips>``): one
process, one Context (``parsec.init(nb_cores=...)``, started once in
set-up), one scheduler, one ``tpuN`` device module a chip; ONE
``TiledMatrix`` (``SymTwoDimBlockCyclic``, lower: the stored triangle
alone) whose tiles the caller advises to the modules over the
configuration's ``device_grid`` (``advise_on_devices``: what upstream's
tester does with ``dplasma_advise_data_on_device``) and puts there; then
``ctx.add_taskpool(build_potrf(A))``, wait, ``block_until_ready`` on the
lower tiles. Nothing else is told to the runtime: that a task runs where
the tile it writes lies, and that a tile of another chip is copied to a
chip once, are its rules. Nothing here computes any part of the factor.

The matrix is ``dpotrf_ptg_host``'s for the same seed. The next matrix is
written over the last factor, tile by tile, every tile made ON the chip
it is advised to (one program a chip: block row j of the generator cut
into that chip's tiles of block column j), one block column in flight on
a chip, before the step starts.

The guarantees ``check`` holds, each a part of ``correct``: the residual;
every task of every step counted once, on the module the tile it writes
is advised to (the modules' counters against ``ops_multidev``'s count of
the graph by chip); every tile of the factor on its advised chip; EVERY
chip's ``peak_bytes_in_use`` under the configuration's multiple of ITS
stored share (read before the probe puts anything on a chip; also held
when the warm step ends, where a program over it stops the run).
"""

from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp

from benchmark import generate, ops, ops_multidev

WAIT_LIMIT_S = 600.0        # a pool that has not ended by then never will
# what the program counts per chip module (``dump_statistics``) and per
# worker (``es.stats``) that the cell's readers want over the window
MODULE_COUNTERS = ("remote_copies", "remote_bytes_in", "remote_hits",
                   "remote_copy_s")
WORKER_COUNTERS = ("tasks_advised", "tasks_on_advised")


class PtgMultidevFactorization:
    def __init__(self, config, sizes, seed, devices, spans, reference):
        self.config, self.seed, self.devices = config, seed, devices
        self.spans, self.ref = spans, reference
        self.n, self.nb = int(sizes["n"]), int(sizes["nb"])
        if self.n % self.nb:
            raise ValueError(f"n={self.n} is not a multiple of "
                             f"nb={self.nb}")
        nt = self.nt = self.n // self.nb
        self.grid = tuple(int(x) for x in config["device_grid"])
        if self.grid[0] * self.grid[1] != len(devices):
            raise ValueError(f"a device grid of {self.grid} over "
                             f"{len(devices)} chips")
        self.ops_per_step = getattr(ops, config["ops"])(self.n)
        self.bytes_per_step = getattr(ops, config["min_bytes"])(self.n, 4)
        # POTRF(k), TRSM(m,k), SYRK(m,k), GEMM(m,n,k) of zpotrf_L
        self.tasks_per_step = (nt + nt * (nt - 1)
                               + nt * (nt - 1) * (nt - 2) // 6)
        self.tasks_by_chip = ops_multidev.potrf_tasks_by_chip(nt, self.grid)
        self.lower = [(i, j) for j in range(nt) for i in range(j, nt)]
        itemsize = jnp.dtype(sizes["dtype"]).itemsize
        self.tile_bytes = self.nb * self.nb * itemsize
        self.min_remote_bytes = ops_multidev.potrf_min_remote_bytes(
            nt, self.nb, itemsize, self.grid)
        # a chip's stored share, and the most it may ever hold
        stored = dict.fromkeys(range(len(devices)), 0)
        for i, j in self.lower:
            stored[self.chip_of(i, j)] += self.tile_bytes
        self.stored_by_chip = stored
        over = config["storage"]["peak_over_stored_limit"]
        self.storage_limit_by_chip = {c: over * b for c, b in stored.items()}
        self.steps_run = 0
        # the program's own counters over the window, for the readers
        # (as in the ptg_factorization driver)
        self.window_counters = {}
        self._counters_before = None
        self.ctx = self.A = None

    def chip_of(self, i: int, j: int) -> int:
        return ops_multidev.chip_of(i, j, self.grid)

    def _tiles(self):
        """The factor's tiles."""
        return [self.A.data_of(key) for key in self.lower]

    # -- set-up: the Context, the collection, the generator ---------------
    def setup(self):
        # a program that cannot advise a collection cannot run this
        # deployment: it stops here, before a Context starts
        from parsec_tpu.data.matrix import (SymTwoDimBlockCyclic,
                                            TiledMatrix, advise_on_devices)
        import parsec_tpu as parsec
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
        self.mods = [d for d in self.ctx.devices.devices
                     if d.name.startswith("tpu")]
        if [m.jax_device for m in self.mods] != list(self.devices):
            raise RuntimeError(
                f"device modules {[m.name for m in self.mods]} for the "
                f"chips {[d.id for d in self.devices]}")
        if self.devices[0].platform == "cpu":
            # a rehearsal takes the chip's path, every body through a
            # device module: beside a real accelerator the registry
            # weights the inline CPU module out, and so does this
            for d in self.ctx.devices.devices:
                if d.name == "cpu":
                    d.weight = 0.01
        self.A = advise_on_devices(
            TiledMatrix(n, n, nb, nb, name="A",
                        dist=SymTwoDimBlockCyclic(1, 1, uplo="lower")),
            grid=self.grid)
        rows, cols = self.grid

        def column(key, j, r):
            """Block row ``j`` of D (``j`` traced: one program a chip),
            transposed and cut into the tiles ``(c, j)``, ``c`` = ``r``,
            ``r + rows``, ..., of A0's block column ``j``: the tiles of
            that column one chip holds. Those with ``c < j`` are not
            stored, and the caller drops them."""
            row = generate.spd_row(key, j, n, nb)
            tiles = []
            for c in range(r, nt, rows):
                t = row[:, c * nb:(c + 1) * nb]
                tiles.append(jnp.where(c == j, 0.5 * (t + t.T), t.T))
            return tiles

        # committed to its chip, as a tile a task made is
        self._column = {
            (r, q): jax.jit(
                lambda key, j, r=r: column(key, j, r),
                out_shardings=jax.sharding.SingleDeviceSharding(
                    self.devices[r * cols + q]))
            for r in range(rows) for q in range(cols)}
        return {"context_s": time.perf_counter() - t0,
                "program_counters": self.window_counters,
                "multidev": {
                    "min_remote_bytes_per_step": self.min_remote_bytes,
                    "stored_bytes_by_chip": self.stored_by_chip,
                    "tasks_by_chip_class":
                        ops_multidev.potrf_tasks_by_chip_class(
                            self.nt, self.grid)}}

    # -- one step ---------------------------------------------------------
    def generate(self, step: int, recycle=None):
        """The matrix of step ``step`` over the last factor, in a fixed
        order, every tile made on its chip, one block column in flight."""
        del recycle                     # the collection itself
        # every step starts from the same collector state, as in the
        # other drivers of the dynamic path
        gc.collect()
        key = generate.step_key(self.seed, step)
        rows, cols = self.grid
        made = None                     # column j - 1's, still being made
        for j in range(self.nt + 1):
            # block column j is made by the chips of grid column j % cols
            # while those of the column before finish theirs: one block
            # column in flight on any chip
            nxt = None if j == self.nt else \
                [self._column[r, j % cols](key, j) for r in range(rows)]
            if made is not None:
                for r, tiles in enumerate(jax.block_until_ready(made)):
                    for c, tile in zip(range(r, self.nt, rows), tiles):
                        if c >= j - 1:
                            self.A.write_tile((c, j - 1), tile)
                del tiles, tile
            made = nxt
        return self.A

    def _peak_by_chip(self):
        """The most each chip has held since the process started (0
        where the platform keeps no such count: a CPU rehearsal)."""
        return {c: (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for c, d in enumerate(self.devices)}

    def _over_storage(self, peaks):
        return {c: p for c, p in peaks.items()
                if p > self.storage_limit_by_chip[c]}

    def step(self, A):
        with self.spans.span("submit"):
            tp = self._build(A)
            self.ctx.add_taskpool(tp)
        with self.spans.span("wait"):
            if not tp.wait_completed(WAIT_LIMIT_S):
                raise RuntimeError(
                    f"the pool had not ended after {WAIT_LIMIT_S} s")
            jax.block_until_ready(self._tiles())
        self.steps_run += 1
        if self.steps_run == 1:
            over = self._over_storage(self._peak_by_chip())
            if over:
                raise RuntimeError(
                    f"the warm step held {over} bytes on a chip, over the "
                    f"configuration's storage guarantee of "
                    f"{self.storage_limit_by_chip}: this program keeps "
                    f"what it reads of other chips")
        return A

    def finite(self, A) -> bool:
        by_chip = {}
        for (i, j), t in zip(self.lower, self._tiles()):
            by_chip.setdefault(self.chip_of(i, j), []).append(t)
        return all(bool(_all_finite(tiles)) for tiles in by_chip.values())

    def counters(self):
        """Tasks each device module ran since the Context started, and
        what else the program counts by module and by worker that the
        cell's readers want: kept from the first reading, when the window
        opens, to the second, their difference left in
        ``window_counters`` (``<counter>`` summed over the chips' modules
        and ``<counter>.<module>`` for each). A program without a counter
        leaves nothing there under its name."""
        more = {}
        stats = self.ctx.devices.dump_statistics()
        for s in stats:
            if not s["name"].startswith("tpu"):
                continue
            for name in MODULE_COUNTERS:
                if name in s:
                    more[name] = more.get(name, 0) + s[name]
                    more[f"{name}.{s['name']}"] = s[name]
        for es in self.ctx.streams:
            for name in WORKER_COUNTERS:
                if name in es.stats:
                    more[name] = more.get(name, 0) + es.stats[name]
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
        """The guarantees of the configuration (the module's docstring),
        the chips' peaks read first: the probe runs on chip 0 and takes
        the other chips' tiles there one at a time."""
        peaks = self._peak_by_chip()
        n, nb = self.n, self.nb
        key = generate.step_key(self.seed, step)
        home = self.devices[0]
        with jax.default_matmul_precision("highest"), \
                jax.default_device(home):
            row = jax.jit(lambda j, key, x, y: self.ref.probe_input_row(
                j, key, x, y, n=n, nb=nb))
            factor_t = jax.jit(self.ref.probe_factor_t)
            factor = jax.jit(self.ref.probe_factor)
            x = self.ref.probe_vectors(key, n)
            y, z, y2 = (jnp.zeros_like(x) for _ in range(3))
            for j in range(self.nt):
                y = row(j, key, x, y)
            for i, j in self.lower:
                z = factor_t(i, j, self.ref.on_probe_chip(
                    A.data_of((i, j)), home), x, z)
            for i, j in self.lower:
                y2 = factor(i, j, self.ref.on_probe_chip(
                    A.data_of((i, j)), home), z, y2)
            err = self.ref.residual(y, y2)
        limit = self.config["correct"]["limit"]
        misplaced = [key for key in self.lower
                     if not isinstance(A.data_of(key), jax.Array) or
                     A.data_of(key).devices() !=
                     {self.devices[self.chip_of(*key)]}]
        by_module = {s["name"]: s["tasks"]
                     for s in self.ctx.devices.dump_statistics()}
        want = {m.name: self.tasks_by_chip[c] * self.steps_run
                for c, m in enumerate(self.mods)}
        on_their_chip = all(by_module[name] == n_ for name, n_ in
                            want.items()) and \
            sum(by_module.values()) == self.tasks_per_step * self.steps_run
        over = self._over_storage(peaks)
        ok = err == err and err <= limit and not misplaced and \
            on_their_chip and not over
        return ok, {"residual": err, "limit": limit,
                    "tiles_off_their_chip": len(misplaced),
                    "tasks_by_module": {k: v for k, v in by_module.items()
                                        if v},
                    "tasks_wanted": want,
                    "peak_bytes_by_chip": peaks,
                    "peak_over_stored_by_chip": {
                        c: round(p / self.stored_by_chip[c], 4)
                        for c, p in peaks.items()},
                    "peak_over_stored_limit":
                        self.config["storage"]["peak_over_stored_limit"]}

    def close(self):
        from parsec_tpu.utils import mca_param
        if self.ctx is not None:
            import parsec_tpu as parsec
            parsec.fini(self.ctx)
        for knob in (*self.config["knobs"], "device.tpu.max_devices"):
            mca_param.unset(knob)


@jax.jit
def _all_finite(tiles):
    return jnp.stack([jnp.isfinite(t).all() for t in tiles]).all()


def build(config, sizes, seed, devices, spans, reference):
    return PtgMultidevFactorization(config, sizes, seed, devices, spans,
                                    reference)
