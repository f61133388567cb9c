"""Run one cell of ``BENCHMARK.json`` once.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips itself and starts no other. It
fails, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for. Set-up (the program's imports, plan, compile or load from the
cache in ``<checkout>/.xla_cache``, data on the device, one warm step; not
the TPU runtime's own start-up, printed as ``backend_up_s``) is timed as
``setup_s``; then steps run back to back for ``--seconds``: a closed loop
with one client, each step one whole problem from submission to
``block_until_ready``, its input made on the device from ``--seed``
between steps. The last output is checked against the configuration's
plain reference outside the window. The last line of standard output is
one JSON object: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and a breakdown of the traced steps.

``--dry-run-cpu[=N]`` rehearses a cell on the CPU at the sizes of the
workload file's ``dry`` block (tests, debugging). Every metric it prints
is renamed ``<name>_cpu_dryrun``: a CPU run says nothing about the chip.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()      # process start, as near as this file sees it

import argparse                                            # noqa: E402
import contextlib                                          # noqa: E402
import glob                                                # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
import traceback                                           # noqa: E402

import jax                                                 # noqa: E402

from benchmark import stats, trace_reduce                  # noqa: E402
from benchmark.manifest import ROOT, Manifest, ManifestError   # noqa: E402

DRY_SUFFIX = "_cpu_dryrun"
STEP_LIMIT_S = 120.0           # a step longer than this has timed out
TRACE_START = 0.25             # of the window, before tracing begins
TRACE_SECONDS = 4.0            # traced at least this long, and two steps


def say(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class Spans:
    """Benchmark-side spans: kept in memory on the host clock, and written
    into the profiler's trace (``bench:<name>``) when one is being taken."""

    def __init__(self) -> None:
        self.records = []                   # (name, start, end)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def seconds(self, name: str, since: float) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records
                   if n == name and t0 >= since)


def _devices(chips: int, dry):
    """The cell's chips, or exit non-zero with no result."""
    want = "tpu"
    if dry:
        want = "cpu"
        jax.config.update("jax_platforms", "cpu")
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            # else the caller (the tests' conftest) fixed the count
            jax.config.update("jax_num_cpu_devices", max(dry, chips))
    try:
        devs = jax.devices()
    except RuntimeError as exc:
        sys.exit(f"benchmark: no TPU — JAX could not bring the backend "
                 f"up: {exc}")
    found = sorted({d.platform for d in devs})
    if found != [want]:
        sys.exit(f"benchmark: no TPU — JAX found only {found} devices. "
                 "Nothing was run. (A CPU rehearsal must be stated: "
                 "--dry-run-cpu)")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chips, JAX found "
                 f"{len(devs)}. Nothing was run.")
    return devs


def _enable_cache() -> str:
    """The program's one cache resolution (JAX_COMPILATION_CACHE_DIR, else
    the fixed ``<checkout>/.xla_cache``), with every program persisted:
    the program's own threshold skips what compiles in under a second,
    and a warm run would compile those again (PERF.md §7)."""
    from parsec_tpu.utils import compile_cache, mca_param
    mca_param.set("jit.cache_dir", "auto")
    cache_dir = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


class _XlaCache:
    """JAX's own count of persistent-cache hits and misses: a warm run
    must find every program it compiles in the cache."""
    EVENTS = {"/jax/compilation_cache/cache_hits": "xla_cache_hits",
              "/jax/compilation_cache/cache_misses": "xla_cache_misses"}

    def __init__(self) -> None:
        from jax import monitoring
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _delta(before, after):
    """Counter deltas over the window, or None where a driver has none."""
    if before is None or after is None:
        return None
    return {k: v - before.get(k, 0) for k, v in after.items()}


class _Tracer:
    """Traces a few steady steps in the middle of the window."""

    def __init__(self, on: bool, out_dir: str, seconds: float) -> None:
        self.state = "waiting" if on else "off"
        self.dir, self.seconds = out_dir, seconds
        self.t_on = 0.0
        self.steps = 0
        self._span = None

    def before_step(self, elapsed: float) -> None:
        if self.state != "waiting" or elapsed < TRACE_START * self.seconds:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # spans, not every Python call
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(
            trace_reduce.SPAN_PREFIX + trace_reduce.WINDOW_SPAN)
        self._span.__enter__()
        self.state, self.t_on = "on", time.perf_counter()

    def after_step(self) -> None:
        if self.state != "on":
            return
        self.steps += 1
        if self.steps >= 2 and \
                time.perf_counter() - self.t_on >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        if self.state != "on":
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def reduced(self):
        if self.state != "done":
            return {}
        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        return trace_reduce.reduce(trace_reduce.load(files[0])) \
            if files else {}


def _warm_up(driver, spans, last) -> float:
    """One step before the window: every shape the window uses, no other.
    Leaves its result in ``last`` for the first generate to recycle."""
    with spans.span("generate"):
        inp = driver.generate(0, None)
    t0 = time.perf_counter()
    last.append(driver.step(inp))
    warm_s = time.perf_counter() - t0
    if not driver.finite(last[0]):
        raise RuntimeError("the warm step returned a non-finite result")
    return warm_s


def _window(driver, spans, last, seconds: float, tracer):
    """Steps back to back for ``seconds``: ``(step_s, attempted, failed)``.
    ``last`` holds the last step's result and nothing else does: the driver
    gets it back to free, or to write the next input over."""
    step_s, attempted, failed = [], 0, 0
    t_win = time.perf_counter()
    while time.perf_counter() - t_win < seconds:
        tracer.before_step(time.perf_counter() - t_win)
        attempted += 1                 # step 0 was the warm step
        try:
            with spans.span("generate"):
                inp = driver.generate(attempted, last.pop())
            t0 = time.perf_counter()
            with spans.span(trace_reduce.STEP_SPAN):
                last.append(driver.step(inp))
            dt = time.perf_counter() - t0
            del inp
            with spans.span("between_steps"):
                ok = driver.finite(last[0]) and dt <= STEP_LIMIT_S
        except Exception:  # noqa: BLE001 — the step failed; report it
            traceback.print_exc(file=sys.stdout)
            last.clear()
            failed += 1
            break
        if ok:
            step_s.append(dt)
        else:
            last.clear()
            failed += 1
        tracer.after_step()
    tracer.stop()
    return step_s, attempted, failed


def run(args, root: str = ROOT):
    """Run the cell; returns the result object (the last line)."""
    man = Manifest(root)
    cell = man.cell(args.workload)
    workload = man.workload(args.workload)
    config = man.config(cell["config"])
    dry = args.dry_run_cpu
    sizes = {**config["sizes"], **workload["traffic"]}
    if dry:
        sizes.update(workload["dry"])

    devs = _devices(cell["chips"], dry)
    used = devs[:cell["chips"]]
    # set-up is timed from here: what comes before (the interpreter, JAX's
    # imports, the TPU runtime's start-up) took 10 to 18 s on the v5e host
    # whatever the cell or the program did, and would drown the rest
    t_up = time.perf_counter()
    from parsec_tpu.utils import compile_cache
    cache_dir = None if dry else _enable_cache()
    compile_cache.backend_compile_count()          # install the counter
    xla_cache = _XlaCache()
    say("device", platform=used[0].platform, kind=used[0].device_kind,
        count=len(devs), used=[d.id for d in used], cache_dir=cache_dir,
        dry_run=bool(dry))
    t_init = time.perf_counter()

    spans = Spans()
    tracer = _Tracer(bool(args.trace), os.path.join(
        root, ".benchmark_trace", args.workload), args.seconds)
    driver = man.driver(config["driver"]).build(
        config, sizes, args.seed, used, spans,
        man.reference(config["reference"]))
    last = []
    try:
        facts = driver.setup()
        warm_s = _warm_up(driver, spans, last)
        t_ready = time.perf_counter()
        setup = dict(facts, backend_up_s=t_up - _T0, setup_s=t_ready - t_up,
                     plan_compile_s=t_ready - t_init, warm_step_s=warm_s,
                     **{k: v for k, v in compile_cache.cache_stats().items()
                        if k in ("backend_compiles", "store_hits",
                                 "store_misses")}, **xla_cache.counts)
        say("setup", **{k: (round(v, 3) if isinstance(v, float) else v)
                        for k, v in setup.items()})

        counters0 = driver.counters()
        compiles0 = compile_cache.backend_compile_count()
        step_s, attempted, failed = _window(driver, spans, last,
                                            args.seconds, tracer)
        window_s = time.perf_counter() - t_ready
        compiles = compile_cache.backend_compile_count() - compiles0
        counters1 = driver.counters()
        peak_bytes = 0 if dry else _peak_bytes(used)

        # outside the window: is the last step's result right?
        correct, detail = False, {"reason": "the last step failed"}
        if last:
            correct, detail = driver.check(last.pop(), attempted)
        say("check", correct=correct, step=attempted, **detail)
    finally:
        last.clear()
        driver.close()

    record = {
        "cell": args.workload, "chips": len(used), "dry": bool(dry),
        "setup": setup,
        "window": {"step_s": step_s, "attempted": attempted,
                   "failed": failed, "compiles": compiles,
                   "window_s": window_s,
                   "tasks_by_module": _delta(
                       counters0.get("tasks_by_module"),
                       counters1.get("tasks_by_module")),
                   "span_s": {n: spans.seconds(n, t_ready) for n in
                              {r[0] for r in spans.records}}},
        "driver": {k: getattr(driver, k) for k in
                   ("ops_per_step", "bytes_per_step", "tasks_per_step")},
        "peak_bytes": peak_bytes,
        "trace": tracer.reduced(),
        "peaks": None if dry else man.peaks(used[0].device_kind),
    }
    if step_s:
        line = {"steps": len(step_s), "sum_step_s": round(sum(step_s), 4),
                "window_s": round(window_s, 4), "p50": stats.median(step_s)}
        tail = stats.tail(step_s)
        if tail:
            line[f"p{tail[0]:g}"] = tail[1]
        say("steps_ms", ms=" ".join(f"{1e3 * t:.1f}" for t in step_s))
        say("window", **line, compiles_in_window=compiles,
            span_s={k: round(v, 4) for k, v in
                    record["window"]["span_s"].items()},
            counters=counters1)

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in man.metrics_for(section, args.workload):
        spec = man.metric(m["name"])
        value = man.reader(spec["reader"]).read(record,
                                                spec.get("params", {}))
        if value is not None:   # a reader that found nothing to read
            metrics[m["name"] + (DRY_SUFFIX if dry else "")] = {
                "value": value, "unit": m["unit"]}

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct and not failed),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    red = record["trace"]
    if red:
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    return result


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-cpu", nargs="?", const=1, type=int,
                    metavar="N", help="CPU rehearsal on N virtual devices "
                    "at the workload's dry sizes; metrics are renamed")
    args = ap.parse_args(argv)
    try:
        result = run(args, root)
    except ManifestError as exc:
        sys.exit(f"benchmark: {exc}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
