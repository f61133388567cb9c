"""Driver: a dense factorization on the compiled path, one chip or a mesh.

One step is one whole factorization: the taskpool the configuration names
(``build_potrf_left``) is planned by ``plan_taskpool``, lowered by
``PanelExecutor`` to ONE XLA program over the matrix in the executor's
dense layout, and the step is that program from call to
``block_until_ready``, its input donated. With ``"sharded": true`` the
same program is compiled by ``compile_with_plan`` over a ``rows`` mesh of
the cell's chips, the state sharded ``P("rows")``.

These are the entry points ``bench.py``'s flagship section and
``chip_smoke.py`` call; the input generator, the operation count and the
check are the benchmark's own.
"""

from __future__ import annotations

import importlib
import time

import jax
from jax import lax

from benchmark import generate, ops


class PanelFactorization:
    def __init__(self, config, sizes, seed, devices, spans, reference):
        self.config, self.seed, self.devices = config, seed, devices
        self.spans, self.ref = spans, reference
        self.n, self.nb = int(sizes["n"]), int(sizes["nb"])
        self.sharded = bool(sizes.get("sharded", False))
        if self.n % (self.nb * len(devices)):
            raise ValueError(
                f"n={self.n} is not a multiple of nb={self.nb} times "
                f"{len(devices)} chips")
        self.ops_per_step = getattr(ops, config["ops"])(self.n)
        self.bytes_per_step = getattr(ops, config["min_bytes"])(self.n, 4)
        self.tasks_per_step = 0          # set by setup() from the plan
        self._fn = self._gen = None

    # -- set-up: plan, compile or load, warm the generator and one step ---
    def setup(self):
        from parsec_tpu.compiled.panels import PanelExecutor
        from parsec_tpu.compiled.wavefront import plan_taskpool
        from parsec_tpu.data.matrix import TiledMatrix
        from parsec_tpu.utils import mca_param

        for knob, value in self.config["knobs"].items():
            mca_param.set(knob, value)
        mod, _, fn = self.config["taskpool"].partition(":")
        build_taskpool = getattr(importlib.import_module(mod), fn)
        n, nb = self.n, self.nb

        t0 = time.perf_counter()
        plan = plan_taskpool(build_taskpool(
            TiledMatrix(n, n, nb, nb, name="A")))
        ex = PanelExecutor(plan)
        self.tasks_per_step = plan.n_tasks
        t_plan = time.perf_counter()
        sharding = None
        if self.sharded:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from parsec_tpu.compiled.spmd import compile_with_plan
            import numpy as np
            mesh = Mesh(np.asarray(self.devices), ("rows",))
            sharding = NamedSharding(mesh, P("rows"))
            self._fn = compile_with_plan(
                ex.run_state, mesh=mesh, in_shardings=({"A": sharding},),
                out_shardings={"A": sharding}, donate_argnums=0,
                example_args=(ex.state_shapes(),),
                fn_key=("benchmark_sharded", ex.monolith_cache_key()))
        else:
            self._fn = ex.jitted        # shared jit store / executor store
        # The next input is written over the last result (donated): a
        # fresh 6.7 GB buffer per step makes the TPU runtime defragment
        # and drop its loaded programs at a whim, and then some steps
        # reload the 0.4 GB program (27 ms) and some do not (PERF.md §6).
        donate = () if self.devices[0].platform == "cpu" else (1,)
        self._gen = jax.jit(
            lambda key, old: generate.spd_matrix(key, n, nb),
            donate_argnums=donate, keep_unused=True, out_shardings=sharding)
        self._blank = jax.jit(
            lambda: jax.numpy.zeros((n, n), jax.numpy.float32),
            out_shardings=sharding)
        t_compile = time.perf_counter()
        return {"plan_s": t_plan - t0, "compile_or_load_s": t_compile - t_plan,
                "n_tasks": plan.n_tasks, "n_waves": plan.n_waves}

    # -- one step ---------------------------------------------------------
    def generate(self, step: int, recycle=None):
        old = recycle["A"] if recycle is not None else self._blank()
        return jax.block_until_ready(
            {"A": self._gen(generate.step_key(self.seed, step), old)})

    def step(self, state):
        return jax.block_until_ready(self._fn(state))

    def finite(self, out) -> bool:
        return bool(_all_finite(out["A"]))

    def counters(self):
        return {}

    # -- outside the window -----------------------------------------------
    def check(self, out, step: int):
        """Residual of the factor of step ``step`` against A₀ rebuilt from
        the seed, one block row at a time. Each chip's block rows are
        moved to the first chip in turn; the probe over a SHARDED factor
        would plan tens of GB of temporaries (PERF.md, PR 21)."""
        n, nb = self.n, self.nb
        key = generate.step_key(self.seed, step)
        first = self.devices[0]
        with jax.default_matmul_precision("highest"):
            probe = jax.jit(
                lambda j, row, key, x, y, y2: self.ref.probe_row(
                    j, row, key, x, y, y2, n=n, nb=nb))
            with jax.default_device(first):
                x = self.ref.probe_vectors(key, n)
                y, y2 = jax.numpy.zeros_like(x), jax.numpy.zeros_like(x)
            shards = sorted(out["A"].addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            for shard in shards:
                r0 = shard.index[0].start or 0
                for j in range(shard.data.shape[0] // nb):
                    row = jax.device_put(_rows(shard.data, j * nb, nb), first)
                    y, y2 = probe(r0 // nb + j, row, key, x, y, y2)
            err = self.ref.residual(y, y2)
        limit = self.config["correct"]["limit"]
        return err == err and err <= limit, {
            "residual": err, "limit": limit,
            "shard_devices": [s.device.id for s in shards]}

    def close(self):
        from parsec_tpu.utils import mca_param
        for knob in self.config["knobs"]:
            mca_param.unset(knob)


@jax.jit
def _all_finite(a):
    return jax.numpy.isfinite(a).all()


_rows = jax.jit(lambda a, start, nb: lax.dynamic_slice_in_dim(
    a, start, nb, 0), static_argnums=2)


def build(config, sizes, seed, devices, spans, reference):
    return PanelFactorization(config, sizes, seed, devices, spans, reference)
