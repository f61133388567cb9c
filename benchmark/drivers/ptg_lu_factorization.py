"""Driver: a tile LU by incremental pivoting as a PTG taskpool on the
dynamic path.

One step is what a DPLASMA user pays for one ``dgetrf_incpiv`` through
the runtime's scheduler (``testing_dgetrf_incpiv -N <n> -t <NB> -i <IB>``,
one accelerator): the taskpool the configuration names
(``build_getrf_incpiv``: zgetrf_incpiv.jdf's classes GETRF, GESSM, TSTRF,
SSSSM over descA, descL and descIPIV) is built over the three
collections, ``ctx.add_taskpool`` unfolds it task by task through the PTG
front end, the Context's workers and the chip's device module, the pool
is waited for, and ``block_until_ready`` on every tile of A, L and IPIV,
where the classes' own write-backs left the factored form. The Context
(``parsec.init(nb_cores=...)``) is started once in set-up. Nothing here
computes any part of the factorization.

A is a full square collection of nb × nb float tiles, L one of ib × nb
tiles (those under the diagonal are written), IPIV one of nb int32 a tile
(those on and under the diagonal); every tile is a ``jax.Array`` committed
to the chip before the first step, the pivots too: no tile is read by the
host inside a step, which the warm step and every step of a CPU rehearsal
check by running with device-to-host transfers disallowed (process-wide:
the tasks run on the Context's worker threads). The matrix is made as
``dplasma_dplrnt`` makes it (uniform in [-0.5, 0.5)), tile (i, j) from
``generate.tile`` with index ``i*nt + j``, over the last factored form,
one block column in flight, before the step starts.

The storage guarantee, the step, the counters and the tear-down are
``ptg_factorization``'s, which this driver extends; beside that driver's
counters it leaves the chip module's ``int_tiles_staged``,
``int_bytes_staged``, ``region_merges``, ``lone_in_place`` and
``groups_in_place`` over the window in ``program_counters``.
"""

from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp

from benchmark import generate, ops_getrf
from benchmark.drivers.ptg_factorization import PtgFactorization

MODULE_COUNTERS = ("int_tiles_staged", "int_bytes_staged", "region_merges",
                   "lone_in_place", "groups_in_place")


class PtgLuFactorization(PtgFactorization):
    def __init__(self, config, sizes, seed, devices, spans, reference):
        # the POTRF driver's fields, for a full square A, its L and IPIV
        self.config, self.seed, self.devices = config, seed, devices
        self.spans, self.ref = spans, reference
        self.n, self.nb = int(sizes["n"]), int(sizes["nb"])
        self.ib = int(sizes["ib"])
        if self.n % self.nb or self.nb % self.ib:
            raise ValueError(f"n={self.n}, nb={self.nb}, ib={self.ib}: "
                             f"each has to divide the one before")
        nt = self.nt = self.n // self.nb
        self.itemsize = jnp.dtype(sizes["dtype"]).itemsize
        self.ops_per_step = ops_getrf.getrf_ops(self.n)
        self.bytes_per_step = ops_getrf.getrf_min_bytes(
            self.n, self.nb, self.ib, self.itemsize)
        self.tasks_by_class = ops_getrf.getrf_tasks(nt)
        self.tasks_per_step = sum(self.tasks_by_class.values())
        self.l_keys = [(m, k) for k in range(nt) for m in range(k + 1, nt)]
        self.p_keys = [(m, k) for k in range(nt) for m in range(k, nt)]
        self.stored_bytes = ops_getrf.getrf_stored_bytes(
            self.n, self.nb, self.ib, self.itemsize)
        self.storage_limit_bytes = \
            config["storage"]["peak_over_stored_limit"] * self.stored_bytes
        self.steps_run = 0
        self.window_counters = {}
        self._counters_before = self._module_before = None
        self.ctx = self.A = self.L = self.IPIV = None

    def _tiles(self):
        """The factored form's tiles: all of A, what is written of L and
        of IPIV."""
        return [self.A.data_of(key) for key in self.A.keys()] + \
            [self.L.data_of(key) for key in self.l_keys] + \
            [self.IPIV.data_of(key) for key in self.p_keys]

    # -- set-up: the Context, the collections, the generator --------------
    def setup(self):
        import parsec_tpu as parsec
        from parsec_tpu.data.matrix import TiledMatrix
        from parsec_tpu.utils import mca_param

        # a tree without the pivoted builder cannot run this deployment,
        # and says so before a Context starts
        mod, _, fn = self.config["taskpool"].partition(":")
        module = importlib.import_module(mod)
        build = getattr(module, fn)
        l_collection = getattr(module, self.config["l_collection"])
        ipiv_collection = getattr(module, self.config["ipiv_collection"])
        self._build = lambda A: build(A, self.L, self.IPIV)
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
        self.L = l_collection(self.A, self.ib)
        self.IPIV = ipiv_collection(self.A)
        # the storage of L and IPIV, on the chip before the first step:
        # the factorization writes into it and makes none
        here = jax.sharding.SingleDeviceSharding(self.devices[0])
        for key in self.l_keys:
            self.L.write_tile(key, jnp.zeros(
                (self.ib, nb), self.L.dtype, device=here))
        for key in self.p_keys:
            self.IPIV.write_tile(key, jnp.zeros(
                (1, nb), self.IPIV.dtype, device=here))

        def column(key, j):
            """The tiles (c, j) of A0's block column ``j`` (``j`` traced:
            one program)."""
            return [generate.tile(key, c * nt + j, nb) for c in range(nt)]

        # committed to the chip, as a tile a task made is
        self._column = jax.jit(column, out_shardings=here)
        kernels = ops_getrf.getrf_kernels(nb, self.ib, self.itemsize)
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

    def step(self, A):
        """``ptg_factorization``'s step; the warm step, and every step of
        a CPU rehearsal, with device-to-host transfers disallowed in the
        whole process: a tile, pivots included, that the host read inside
        a step raises there."""
        if self.steps_run and self.devices[0].platform != "cpu":
            return super().step(A)
        was = jax.config.jax_transfer_guard_device_to_host
        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
        try:
            return super().step(A)
        finally:
            jax.config.update("jax_transfer_guard_device_to_host", was)

    def counters(self):
        """``ptg_factorization``'s counters and, summed over the chip
        modules, those of ``MODULE_COUNTERS`` the program keeps."""
        out = super().counters()
        mine = {}
        for s in self.ctx.devices.dump_statistics():
            if s["name"].startswith("tpu"):
                for name in MODULE_COUNTERS:
                    if name in s:
                        mine[name] = mine.get(name, 0) + s[name]
        if self._module_before is None:
            self._module_before = mine
        else:
            self.window_counters.update(
                {name: n - self._module_before.get(name, 0)
                 for name, n in mine.items()})
        out["program_counters"].update(mine)
        return out

    # -- outside the window -----------------------------------------------
    def readings(self, A, step: int):
        """What the factored form the timed step left in A, L and IPIV
        reads against A0 rebuilt from the seed, for 8 probe vectors x,
        every product at highest precision, the transformation applied
        from the tiles by the plain reference:
        ``residual`` ‖A0 x − M (U x)‖ / ‖A0 x‖, M the inverse of the stored
        transformation; ``solve`` ‖A0 x̂ − b‖ / (‖A0‖_F ‖x̂‖) for b = A0 x
        and x̂ = U⁻¹ apply_l(b); ``multipliers`` the largest of |L_kk|,
        |L21| and |L11 − I|; ``low_bits`` the largest share, over the
        float tiles, of entries a bfloat16 holds exactly; ``pivots_valid``
        every IPIV tile a permutation or a block's interchanges."""
        ref, nt, nb, ib = self.ref, self.nt, self.nb, self.ib
        key = generate.step_key(self.seed, step)
        a = lambda i, j: A.data_of((i, j))                      # noqa: E731
        low = lambda i, j: self.L.data_of((i, j))               # noqa: E731
        piv = lambda i, j: self.IPIV.data_of((i, j))            # noqa: E731
        with jax.default_matmul_precision("highest"):
            row = jax.jit(lambda i, key, x, y, sq: ref.probe_input_row(
                i, key, x, y, sq, nt=nt, nb=nb))
            u_tile = jax.jit(ref.probe_u)
            with jax.default_device(self.devices[0]):
                x = ref.probe_vectors(key, self.n)
                zero = jnp.zeros((), jnp.float32)

            def a0_times(v):
                y, sq = jnp.zeros_like(v), zero
                for i in range(nt):
                    y, sq = row(i, key, v, y, sq)
                return y, sq

            y, sq = a0_times(x)
            ux = jnp.zeros_like(x)
            for i in range(nt):
                for j in range(i, nt):
                    ux = u_tile(i, j, a(i, j), x, ux)
            mux = ref.apply_l_inverse(a, low, piv, ux, nt)
            xh = ref.solve_u(a, ref.apply_l(a, low, piv, y, nt), nt)
            yh, _ = a0_times(xh)
            mult = [ref.multipliers_diagonal(a(k, k)) for k in range(nt)] + \
                [ref.multipliers_pair(a(m, k), low(m, k))
                 for m, k in self.l_keys]
            bits = [ref.low_bits_share(a(i, j)) for i, j in A.keys()] + \
                [ref.low_bits_share(low(m, k)) for m, k in self.l_keys]
            valid = [ref.permutation_valid(piv(k, k)) for k in range(nt)] + \
                [ref.interchanges_valid(piv(m, k), ib)
                 for m, k in self.l_keys]
            return {"residual": ref.norm(y - mux) / ref.norm(y),
                    "solve": ref.norm(yh - y) /
                    (float(jnp.sqrt(sq)) * ref.norm(xh)),
                    "multipliers": float(jnp.max(jnp.stack(mult))),
                    "low_bits": float(jnp.max(jnp.stack(bits))),
                    "pivots_valid": bool(jnp.all(jnp.stack(valid)))}

    def check(self, A, step: int):
        """The readings under their limits; every tile of A, L and IPIV
        on the chip; every task of every step counted once on the chip's
        module; the storage guarantee."""
        got = self.readings(A, step)
        correct = self.config["correct"]
        limits = {"residual": correct["limit"],
                  "solve": correct["solve_limit"],
                  "multipliers": correct["multipliers_limit"],
                  "low_bits": correct["low_bits_limit"]}
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
            and got["pivots_valid"] and on_chip and \
            tasks_on_chip == sum(by_module.values()) == tasks and \
            peak <= self.storage_limit_bytes
        detail = {}
        for k in limits:
            detail[k], detail[k + "_limit"] = got[k], limits[k]
        detail.update(pivots_valid=got["pivots_valid"],
                      factored_form_on_chip=on_chip, peak_bytes=peak,
                      storage_limit_bytes=round(self.storage_limit_bytes),
                      tasks_on_chip=tasks_on_chip, tasks_of_the_steps=tasks)
        return ok, detail


def build(config, sizes, seed, devices, spans, reference):
    return PtgLuFactorization(config, sizes, seed, devices, spans, reference)
