"""Driver: a tiled factorization by task insertion on the dynamic path.

One step is what a DPLASMA user pays for one ``dpotrf`` through the DTD
interface (``testing_dpotrf_dtd -N <n> -t <NB>``, one accelerator): a new
``dtd.Taskpool``, ``ctx.add_taskpool``, the insertion loop the
configuration names (``insert_potrf_dtd``: the tester's sequential loop
of POTRF, TRSM, SYRK/HERK and GEMM inserts with its priorities, a
``flush_tile`` per finished tile, one ``flush_all``), ``tp.wait()``, and
``block_until_ready`` on the lower tiles, where every task's completion
left its update. The runtime discovers the DAG from the tiles' access
modes while the tasks inserted before already run on the chip. Nothing
here computes any part of the factor.

Everything else is ``ptg_factorization``'s, which this driver extends:
the Context (``parsec.init(nb_cores=...)``, started once in set-up), the
collection (the lower triangle alone, every tile a ``jax.Array``
committed to the chip), the matrix (``dpotrf_panel``'s for the same
seed), the next matrix written over the last factor one block column in
flight, the storage guarantee held at the warm step and over the window,
and the check. So the two POTRF deployments differ by the front end
alone. A program without ``insert_potrf_dtd`` stops in ``setup()``,
before it has started a Context.

Which task engine runs (``python``/``native``) is the runtime's choice
and is printed. ``engine_for`` declines a real accelerator, so a chip
gets the Python engine; a CPU rehearsal asks for the same one (it is
there to take the chip's path: the device module's launches, the
stacked TRSM, the flushes), as it weights the inline CPU module out.
"""

from __future__ import annotations

import jax

from benchmark.drivers.ptg_factorization import WAIT_LIMIT_S, PtgFactorization

_ENGINE_KNOB = "runtime.native_dtd"


class DtdFactorization(PtgFactorization):
    engine = None
    _engine_asked = None        # the knob as set-up found it (a rehearsal)
    _dtd_before = None

    def setup(self):
        from parsec_tpu.utils import mca_param
        if self.devices[0].platform == "cpu":
            self._engine_asked = mca_param.override_of(_ENGINE_KNOB)
            mca_param.set(_ENGINE_KNOB, 0)
        return super().setup()

    def step(self, A):
        from parsec_tpu import dtd
        with self.spans.span("insert"):
            # one name for every factorization: the Context keeps
            # terminated pools by name
            tp = dtd.Taskpool("potrf_dtd")
            self.ctx.add_taskpool(tp)
            self._build(tp, A)
        with self.spans.span("wait"):
            if not tp.wait(timeout=WAIT_LIMIT_S):
                raise RuntimeError(
                    f"the pool had not ended after {WAIT_LIMIT_S} s")
            jax.block_until_ready(self._tiles())
        self.engine = "native" if tp._native is not None else "python"
        self.steps_run += 1
        if self.steps_run == 1 and \
                self._peak_bytes() > self.storage_limit_bytes:
            raise RuntimeError(
                f"the warm step held {self._peak_bytes()} bytes on the "
                f"chip, over the configuration's storage guarantee of "
                f"{self.storage_limit_bytes:.0f}: this program does not "
                f"factor in the matrix's own storage")
        return A

    def counters(self):
        """The PTG driver's, and what the DTD front end counted in the
        pools that ended while the program's stage timers were on
        (``Context.dtd_counters``: sums, and ``*_peak`` the largest
        seen), first reading taken from the last as there."""
        out = super().counters()
        dtd = dict(self.ctx.dtd_counters)
        if self._dtd_before is None:
            self._dtd_before = dtd
        else:
            self.window_counters.update(
                {name: n if name.endswith("_peak")
                 else n - self._dtd_before.get(name, 0)
                 for name, n in dtd.items()})
        out["program_counters"].update(dtd)
        return dict(out, engine=self.engine)

    def check(self, A, step: int):
        ok, detail = super().check(A, step)
        return ok, dict(detail, engine=self.engine)

    def close(self):
        from parsec_tpu.utils import mca_param
        super().close()
        if self._engine_asked is not None:
            mca_param.restore_override(_ENGINE_KNOB, self._engine_asked)


def build(config, sizes, seed, devices, spans, reference):
    return DtdFactorization(config, sizes, seed, devices, spans, reference)
