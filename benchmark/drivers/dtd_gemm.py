"""Driver: tiled GEMM by dynamic task insertion, bodies on the chip.

One step is what a PaRSEC user pays for one product C ← A·B + C through
the dynamic runtime: a new ``dtd.Taskpool``, ``ctx.add_taskpool``, the
insertion loop (``insert_gemm_dtd``: one task per C tile and k block),
``tp.wait()``, and ``block_until_ready`` on the C tiles. The Context
(``parsec.init(nb_cores=...)``: host scheduler, worker threads, one
device module per chip) is started once in set-up; A and B stay on the
device across steps, every step gets a fresh C made from the seed.

Which task engine runs (``python``/``native``) is the runtime's choice and
is printed, not chosen here.
"""

from __future__ import annotations

import gc
import time

import jax

from benchmark import generate, ops

_KEY_A, _KEY_B = (1 << 30), (1 << 30) + 1     # never a step index


class DtdGemm:
    def __init__(self, config, sizes, seed, devices, spans, reference):
        self.config, self.seed, self.devices = config, seed, devices
        self.spans, self.ref = spans, reference
        m, n, k, nb = (int(sizes[x]) for x in ("m", "n", "k", "nb"))
        if m % nb or n % nb or k % nb:
            raise ValueError(f"m={m}, n={n}, k={k} are not multiples of "
                             f"nb={nb}")
        self.m, self.n, self.k, self.nb = m, n, k, nb
        self.ops_per_step = ops.gemm_ops(m, n, k)
        self.bytes_per_step = ops.tiled_gemm_min_bytes(m, n, k, nb, 4)
        self.tasks_per_step = ops.tiled_gemm_tasks(m, n, k, nb)
        self.ctx = self.engine = None

    def _matrix(self, name, rows, cols, key):
        from parsec_tpu.data.matrix import TiledMatrix
        mat = TiledMatrix(rows, cols, self.nb, self.nb, name=name)
        for i, tile_key in enumerate(mat.keys()):
            mat.write_tile(tile_key, self._tile(key, i))
        return mat

    @staticmethod
    def _tiles(m):
        return [m.data_of(k) for k in m.keys()]

    # -- set-up: the Context, A and B on the device -----------------------
    def setup(self):
        import parsec_tpu as parsec
        from parsec_tpu.utils import mca_param

        for knob, value in self.config["knobs"].items():
            mca_param.set(knob, value)
        # one device module per chip of the cell, whatever else is visible
        mca_param.set("device.tpu.max_devices", len(self.devices))
        nb = self.nb
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
        t_ctx = time.perf_counter()
        self._tile = jax.jit(lambda key, i: generate.tile(key, i, nb))
        base = jax.random.PRNGKey(self.seed)
        self.A = self._matrix("A", self.m, self.k,
                              jax.random.fold_in(base, _KEY_A))
        self.B = self._matrix("B", self.k, self.n,
                              jax.random.fold_in(base, _KEY_B))
        jax.block_until_ready(self._tiles(self.A) + self._tiles(self.B))
        return {"context_s": t_ctx - t0,
                "resident_s": time.perf_counter() - t_ctx}

    # -- one step ---------------------------------------------------------
    def generate(self, step: int, recycle=None):
        # the last C goes before the next is made; a terminated DTD
        # taskpool is cyclic garbage that holds its tiles until a
        # collection (PERF.md §7)
        recycle = None
        gc.collect()
        c = self._matrix("C", self.m, self.n,
                         generate.step_key(self.seed, step))
        jax.block_until_ready(self._tiles(c))
        return c

    def step(self, c):
        from parsec_tpu import dtd
        from parsec_tpu.algorithms import insert_gemm_dtd
        with self.spans.span("insert"):
            # one name for every product: the Context keeps terminated
            # pools by name, and a new name per step would keep a C each
            tp = dtd.Taskpool("gemm")
            self.ctx.add_taskpool(tp)
            insert_gemm_dtd(tp, self.A, self.B, c)
        with self.spans.span("wait"):
            tp.wait()
            jax.block_until_ready(self._tiles(c))
        self.engine = "native" if tp._native is not None else "python"
        return c

    def finite(self, c) -> bool:
        return bool(_all_finite(self._tiles(c)))

    def counters(self):
        """Tasks each device module ran since the Context started."""
        return {"tasks_by_module": {
            s["name"]: s["tasks"]
            for s in self.ctx.devices.dump_statistics()},
            "engine": self.engine}

    # -- outside the window -----------------------------------------------
    def check(self, c, step: int):
        """Relative Frobenius error of C against one matmul of the
        concatenated tiles, a block row at a time."""
        mt, nt, kt = (x // self.nb for x in (self.m, self.n, self.k))
        key = generate.step_key(self.seed, step)
        a, b, got = (self._tiles(x) for x in (self.A, self.B, c))
        with jax.default_matmul_precision("highest"):
            b_full = jax.jit(self.ref.concat_tiles, static_argnums=(1, 2))(
                b, kt, nt)
            row_error = jax.jit(self.ref.row_error)
            num = den = 0.0
            for i in range(mt):
                c0 = [self._tile(key, t) for t in range(i * nt, (i + 1) * nt)]
                e, r = row_error(a[i * kt:(i + 1) * kt], b_full, c0,
                                 got[i * nt:(i + 1) * nt])
                num, den = num + float(e), den + float(r)
        err = (num / den) ** 0.5
        limit = self.config["correct"]["limit"]
        return err == err and err <= limit, {
            "rel_frobenius": err, "limit": limit, "engine": self.engine}

    def close(self):
        import parsec_tpu as parsec
        from parsec_tpu.utils import mca_param
        if self.ctx is not None:
            parsec.fini(self.ctx)
        for knob in (*self.config["knobs"], "device.tpu.max_devices"):
            mca_param.unset(knob)


@jax.jit
def _all_finite(tiles):
    return jax.numpy.stack(
        [jax.numpy.isfinite(t).all() for t in tiles]).all()


def build(config, sizes, seed, devices, spans, reference):
    return DtdGemm(config, sizes, seed, devices, spans, reference)
