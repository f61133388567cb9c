"""Driver: a tile LU with partial pivoting over whole panels as a PTG
taskpool on the dynamic path.

One step is what a DPLASMA user pays for one ``dgetrf_1d`` through the
runtime's scheduler (``testing_dgetrf_1d -N <n> -t <NB>``, one
accelerator): the taskpool the configuration names (``build_getrf_1d``:
zgetrf_1d.jdf's classes GETRF, SWPTRSM, GEMM, SWPBACK over descA and
IPIV) is built over the two collections, ``ctx.add_taskpool`` unfolds it
task by task through the PTG front end, the Context's workers and the
chip's device module, the pool is waited for, and ``block_until_ready``
on every tile of A and IPIV, where the classes' own write-backs left
LAPACK's factored form. Nothing here computes any part of it.

The collections, the matrix (``dgetrf_incpiv_ptg_host``'s for the same
seed), the next matrix written over the last factored form one block
column in flight, the step under the device-to-host transfer guard, the
check's frame (readings under their limits, every tile on the chip, every
task counted once on the chip's module, the storage guarantee) and the
tear-down are ``ptg_lu_factorization``'s, which this driver extends with
the configuration's own collections, counts, counters and readings.
Beside ``ptg_factorization``'s counters it leaves the chip module's
``ranged_tiles_staged``, ``ranged_launches``, ``int_tiles_staged``,
``lone_in_place``, ``groups_in_place`` and the workers'
``ranged_scatters`` over the window in ``program_counters``.
"""

from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp

from benchmark import generate, ops_getrf_1d
from benchmark.drivers.ptg_factorization import PtgFactorization
from benchmark.drivers.ptg_lu_factorization import PtgLuFactorization

MODULE_COUNTERS = ("ranged_tiles_staged", "ranged_launches",
                   "int_tiles_staged", "int_bytes_staged", "lone_in_place",
                   "groups_in_place")
WORKER_COUNTERS = ("ranged_scatters",)


class PtgLu1dFactorization(PtgLuFactorization):
    def __init__(self, config, sizes, seed, devices, spans, reference):
        self.config, self.seed, self.devices = config, seed, devices
        self.spans, self.ref = spans, reference
        self.n, self.nb = int(sizes["n"]), int(sizes["nb"])
        self.ib = int(sizes["ib"])
        if self.n % self.nb or self.nb % self.ib:
            raise ValueError(f"n={self.n}, nb={self.nb}, ib={self.ib}: "
                             f"each has to divide the one before")
        nt = self.nt = self.n // self.nb
        self.itemsize = jnp.dtype(sizes["dtype"]).itemsize
        self.ops_per_step = ops_getrf_1d.getrf_ops(self.n)
        self.bytes_per_step = ops_getrf_1d.getrf_1d_min_bytes(
            self.n, self.itemsize)
        self.tasks_by_class = ops_getrf_1d.getrf_1d_tasks(nt)
        self.tasks_per_step = sum(self.tasks_by_class.values())
        self.stored_bytes = ops_getrf_1d.getrf_1d_stored_bytes(
            self.n, self.itemsize)
        self.storage_limit_bytes = \
            config["storage"]["peak_over_stored_limit"] * self.stored_bytes
        self.steps_run = 0
        self.window_counters = {}
        self._counters_before = self._module_before = None
        self.ctx = self.A = self.IPIV = None

    def _tiles(self):
        """The factored form's tiles: all of A, and IPIV."""
        return [self.A.data_of(key) for key in self.A.keys()] + \
            [self.IPIV.data_of((k, 0)) for k in range(self.nt)]

    # -- set-up: the Context, the collections, the generator --------------
    def setup(self):
        import parsec_tpu as parsec
        from parsec_tpu.data.matrix import TiledMatrix
        from parsec_tpu.utils import mca_param

        # a tree without the panel-pivoted builder cannot run this
        # deployment, and says so before a Context starts
        mod, _, fn = self.config["taskpool"].partition(":")
        module = importlib.import_module(mod)
        try:
            build = getattr(module, fn)
            ipiv_collection = getattr(module, self.config["ipiv_collection"])
        except AttributeError as exc:
            raise SystemExit(
                f"benchmark: this program cannot run dgetrf_1d_ptg_host: "
                f"{exc}") from None
        self._build = lambda A: build(A, self.IPIV, ib=self.ib)
        for knob, value in self.config["knobs"].items():
            mca_param.set(knob, value)
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
            # a rehearsal takes the chip's path (ptg_factorization's rule)
            for d in self.ctx.devices.devices:
                if d.name == "cpu":
                    d.weight = 0.01
        self.A = TiledMatrix(n, n, nb, nb, name="A")
        self.IPIV = ipiv_collection(self.A)
        # IPIV's storage, on the chip before the first step: the
        # factorization writes into it and makes none
        here = jax.sharding.SingleDeviceSharding(self.devices[0])
        for k in range(nt):
            self.IPIV.write_tile((k, 0), jnp.zeros(
                (1, nb), self.IPIV.dtype, device=here))

        def column(key, j):
            """The tiles (c, j) of A0's block column ``j`` (``j`` traced:
            one program)."""
            return [generate.tile(key, c * nt + j, nb) for c in range(nt)]

        self._column = jax.jit(column, out_shardings=here)
        kernels = ops_getrf_1d.getrf_1d_kernels(nt, nb, self.itemsize)
        return {"context_s": time.perf_counter() - t0,
                "program_counters": self.window_counters,
                # per class: tasks a step, operations and least bytes a
                # task (device_seconds_by_program divides by these)
                "kernels": {cls: [self.tasks_by_class[cls], *kernels[cls]]
                            for cls in kernels}}

    def counters(self):
        """``ptg_factorization``'s counters and, summed over the chip
        modules and the workers, those the program keeps of its ranged
        flows and of its in-place launches."""
        out = PtgFactorization.counters(self)
        mine = {}
        for s in self.ctx.devices.dump_statistics():
            if s["name"].startswith("tpu"):
                for name in MODULE_COUNTERS:
                    if name in s:
                        mine[name] = mine.get(name, 0) + s[name]
        for es in self.ctx.streams:
            for name in WORKER_COUNTERS:
                if name in es.stats:
                    mine[name] = mine.get(name, 0) + es.stats[name]
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
        """What the factored form the timed step left in A and IPIV reads
        against A0 rebuilt from the seed, for 8 probe vectors x, every
        product at highest precision, P, L and U applied from the tiles
        by the plain reference: ``residual`` ‖P A0 x − L (U x)‖ / ‖A0 x‖;
        ``solve`` ‖A0 x̂ − b‖ / (‖A0‖_F ‖x̂‖) for b = A0 x and
        x̂ = U⁻¹ L⁻¹ P b (what dgetrs goes on to do); ``multipliers`` the
        largest |L_ij| over ALL of L's strict lower triangle, every tile;
        ``low_bits`` the largest share, over the tiles of A, of entries a
        bfloat16 holds exactly; ``pivots_valid`` entry j of panel k's
        IPIV in [j, (nt − k)·nb)."""
        ref, nt, nb = self.ref, self.nt, self.nb
        key = generate.step_key(self.seed, step)
        a = lambda i, j: A.data_of((i, j))                      # noqa: E731
        piv = lambda k: self.IPIV.data_of((k, 0))               # noqa: E731
        with jax.default_matmul_precision("highest"):
            row = jax.jit(lambda i, key, x, y, sq: ref.probe_input_row(
                i, key, x, y, sq, nt=nt, nb=nb))
            with jax.default_device(self.devices[0]):
                x = ref.probe_vectors(key, self.n)
                zero = jnp.zeros((), jnp.float32)

            def a0_times(v):
                y, sq = jnp.zeros_like(v), zero
                for i in range(nt):
                    y, sq = row(i, key, v, y, sq)
                return y, sq

            y, sq = a0_times(x)
            lux = ref.apply_l(a, ref.apply_u(a, x, nt), nt)
            py = ref.apply_p(piv, y, nt)
            xh = ref.solve_u(a, ref.solve_l(a, py, nt), nt)
            yh, _ = a0_times(xh)
            mult = [ref.multipliers(i, j, a(i, j))
                    for j in range(nt) for i in range(j, nt)]
            bits = [ref.low_bits_share(a(i, j)) for i, j in A.keys()]
            valid = [ref.pivots_valid(k, piv(k), nt) for k in range(nt)]
            return {"residual": ref.norm(py - lux) / ref.norm(y),
                    "solve": ref.norm(yh - y) /
                    (float(jnp.sqrt(sq)) * ref.norm(xh)),
                    "multipliers": float(jnp.max(jnp.stack(mult))),
                    "low_bits": float(jnp.max(jnp.stack(bits))),
                    "pivots_valid": bool(jnp.all(jnp.stack(valid)))}


def build(config, sizes, seed, devices, spans, reference):
    return PtgLu1dFactorization(config, sizes, seed, devices, spans,
                                reference)
