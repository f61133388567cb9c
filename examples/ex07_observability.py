"""Ex07: observability + runtime knobs — PINS counters, trace export,
group launches, and the THREAD_MULTIPLE comm option.

Shows the round-4 surfaces working together on a DTD GEMM:
- ``pins=counters`` (the pins/papi analog): per-task-class rusage/wall
  deltas sampled at EXEC begin/end;
- ``Trace`` with Chrome-trace export (open the JSON in Perfetto);
- group launches: a worker that selects several ready pure DTD bodies
  of one signature has the device module issue them as one program
  (``batches`` / ``batched_tasks`` in the module's statistics);
- ``comm.thread_multiple`` is a knob of the multi-process socket engine
  (see tests/test_socket_comm.py for 2-rank runs) — single-process runs
  here, so it is only printed, not exercised.

Run with JAX_PLATFORMS=cpu for a quick local check.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import parsec_tpu as parsec
    from parsec_tpu import dtd
    from parsec_tpu.algorithms import insert_gemm_dtd
    from parsec_tpu.data import TiledMatrix
    from parsec_tpu.profiling import Counters, Trace
    from parsec_tpu.utils import mca_param

    n, nb = 256, 64
    rng = np.random.default_rng(0)
    A_h = rng.standard_normal((n, n)).astype(np.float32)
    B_h = rng.standard_normal((n, n)).astype(np.float32)

    ctx = parsec.init(nb_cores=2)
    counters = Counters().install(ctx)
    trace = Trace().install(ctx)
    ctx.start()

    A = TiledMatrix.from_array(A_h, nb, nb, name="A")
    B = TiledMatrix.from_array(B_h, nb, nb, name="B")
    C = TiledMatrix.from_array(np.zeros((n, n), np.float32), nb, nb,
                               name="C")
    tp = dtd.Taskpool("gemm")
    ctx.add_taskpool(tp)
    insert_gemm_dtd(tp, A, B, C)
    tp.wait()

    ref = A_h @ B_h
    err = np.abs(C.to_array() - ref).max() / np.abs(ref).max()
    assert err < 1e-2, f"GEMM wrong: {err:.2e}"
    print(f"GEMM ok, rel err {err:.2e}")

    # NOTE: the members of a group launch begin and end together on
    # the worker that formed the group, so each member's deltas span
    # the whole group. A task a device completes on another thread
    # (HookReturn.ASYNC) is counted as async_tasks, wall time only.
    print("\npins/counters (papi analog) per task class:")
    for cls, tot in counters.report().items():
        print(f"  {cls}: tasks={int(tot['tasks'])} "
              f"wall={tot['wall_s']*1e3:.1f}ms "
              f"async={int(tot.get('async_tasks', 0))} "
              f"utime={tot.get('utime_s', 0)*1e3:.1f}ms "
              f"minflt={int(tot.get('minflt', 0))}")

    stats = [d.dump_statistics() for d in ctx.devices.devices
             if d.name.startswith("tpu")]
    batched = sum(s.get("batched_tasks", 0) for s in stats)
    batches = sum(s.get("batches", 0) for s in stats)
    print(f"\ngroup launches: {batched} tasks in {batches} launches")

    out = os.path.join(tempfile.gettempdir(), "ex07_trace.json")
    trace.dump_chrome_trace(out)
    print(f"Chrome trace written to {out} (open in Perfetto)")

    # ISSUE 9: the always-on metrics plane — Prometheus text +
    # JSON statusz, no listener needed (set --mca
    # serving.metrics_port 9100 for the HTTP /metrics + /statusz)
    print("\n/metrics excerpt:")
    for line in ctx.metrics_text().splitlines():
        if line.startswith(("parsec_tasks_completed_total",
                            "parsec_sched_ready_tasks")):
            print(" ", line)
    sz = ctx.statusz()
    print(f"statusz: scheduler={sz['scheduler']} "
          f"streams={len(sz['streams'])} "
          f"metric_families={len(sz['metrics'])}")
    # request tracing: submissions through Context.submit mint a
    # rid; `python -m parsec_tpu.profiling.tools critpath <rid>
    # rank*.json` prints the admission/queue/exec/wire breakdown
    print(f"\ncomm.thread_multiple = "
          f"{mca_param.get('comm.thread_multiple', 0)} "
          "(socket-engine knob; see tests/test_socket_comm.py)")

    counters.uninstall()
    parsec.fini(ctx)


if __name__ == "__main__":
    main()
