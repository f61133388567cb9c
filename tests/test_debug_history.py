"""Debug-history ring (PARSEC_DEBUG_HISTORY analog): per-thread marks,
interleaved dump, runtime wiring."""

import threading

import numpy as np

from parsec_tpu.utils import debug_history, mca_param


def _with_size(size):
    mca_param.set("debug.history_size", size)


def setup_function(_fn):
    # another file's tasks, run with the ring on in this worker process
    # before this file, leave their marks behind
    debug_history.purge()


def teardown_function(_fn):
    mca_param.unset("debug.history_size")
    debug_history.purge()


def test_disabled_is_noop():
    debug_history.mark("never %d", 1)
    assert debug_history.dump() == []


def test_ring_bounds_and_order():
    _with_size(4)
    for i in range(10):
        debug_history.mark("ev %d", i)
    lines = debug_history.dump()
    assert len(lines) == 4                  # ring kept only the tail
    assert "ev 9" in lines[-1] and "ev 6" in lines[0]
    debug_history.purge()
    assert debug_history.dump() == []


def test_threads_interleave_by_time():
    _with_size(16)

    def worker(tag):
        for i in range(3):
            debug_history.mark("%s-%d", tag, i)

    ts = [threading.Thread(target=worker, args=(t,)) for t in "ab"]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    lines = debug_history.dump(purge=True)
    assert len(lines) == 6
    stamps = [float(l.split("]")[0][1:]) for l in lines]
    assert stamps == sorted(stamps)         # merged by timestamp


def test_runtime_marks_execution(ctx):
    """EXE marks are recorded for host-runtime tasks when enabled."""
    import parsec_tpu as parsec
    from parsec_tpu import dtd
    from parsec_tpu.data import LocalCollection
    _with_size(64)
    store = LocalCollection("S", {("x",): np.float32(0)})
    tp = dtd.Taskpool("dh")
    ctx.add_taskpool(tp)
    for _ in range(3):
        tp.insert_task(lambda x: x + 1,
                       dtd.TileArg(store, ("x",), dtd.INOUT))
    tp.wait()
    lines = debug_history.dump(purge=True)
    assert sum("EXE " in l for l in lines) >= 3
