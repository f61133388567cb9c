"""The documents a new owner reads first are held to the tree: the
commands they quote exist, the knobs they tabulate are registered, and
the whole set of MCA knobs is one list a reviewer sees change.

The knobs are read from the sources (``mca_param.register("name", …)``
calls, by ``ast``) and not from the live registry: ``mca_param.set`` of
a name nobody registered adds an entry there, and other tests of the
same process do that.
"""

import ast
import functools
import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCUMENTS = ("README.md", ".claude/skills/verify/SKILL.md")

# the one script of the documents that its reader writes (SKILL.md's
# drive recipe gives its shape)
WRITTEN_BY_THE_READER = {"drive.py"}

# Every MCA knob of the package, sorted. A new knob is one line here and
# a reviewer asks which two callers need different values of it; a knob
# that goes takes its line with it.
KNOBS = """
analysis.lint
analysis.lint_max_tasks
arena.max_cached_bytes
arena.max_used_bytes
comm.aggregate
comm.bcast
comm.bcast_fanout
comm.bcast_topology
comm.bind_core
comm.device_direct
comm.device_pipeline
comm.eager_limit
comm.elastic
comm.fault_inject
comm.fault_inject_after
comm.fault_inject_delay_s
comm.fault_inject_rank
comm.fault_inject_seed
comm.fault_inject_unit
comm.rdv_push
comm.rejoin
comm.rejoin_timeout
comm.segment_bytes
comm.stage_recv
comm.thread_multiple
comm.wireup_timeout_s
debug.history_size
device.hbm_budget_mb
device.hbm_prefetch
device.tpu.enabled
device.tpu.max_devices
dtd.threshold_size
dtd.window_size
gemm.k_block
getrf.trsm_hook
jit.cache_dir
jit.cache_salt
jit.persist_executors
native.sanitize
ops.flash_attention_block_k
ops.flash_attention_block_q
ops.matmul_precision
ops.panel_qr
ops.tri_base
pins
potrf.blocked_tile_chol
potrf.trsm_hook
profiling.dot
profiling.metrics
profiling.native_ring_events
profiling.straggler_factor
profiling.straggler_min_samples
profiling.straggler_window
profiling.trace_max_events
profiling.trace_max_native_sources
runtime.backoff_max_us
runtime.backoff_min_us
runtime.bind_workers
runtime.binding_list
runtime.bypass_chain
runtime.ckpt_dir
runtime.ckpt_interval
runtime.ckpt_interval_s
runtime.lineage
runtime.native_deps
runtime.native_dtd
runtime.nb_cores
runtime.stage_reads
runtime.stage_timers
sched
serving.autoscale
serving.autoscale_cooldown_s
serving.autoscale_down_backlog
serving.autoscale_headroom
serving.autoscale_idle_rounds
serving.autoscale_max_ranks
serving.autoscale_min_ranks
serving.autoscale_poll_s
serving.autoscale_up_backlog
serving.backpressure_timeout_s
serving.deadline_poll_s
serving.drain_timeout_s
serving.kv_decode_window
serving.kv_page_tokens
serving.kv_pages
serving.kv_prefill_chunk
serving.kv_prefill_interleave
serving.kv_prefix_cache
serving.kv_spec_draft
serving.kv_spec_patience_ms
serving.kv_spec_weight
serving.kv_spec_window
serving.metrics_port
serving.migrate_timeout_s
serving.shed_overhead_us
serving.shed_watermark
serving.strict_fair
serving.tenant_backpressure
serving.tenant_hbm_mb
serving.tenant_max_pools
serving.tenant_window
termdet
vpmap
""".split()


@functools.lru_cache(maxsize=None)
def _registered():
    """``{knob: [where registered]}`` from every
    ``<mca_param>.register("name", …)`` call of the package, the module
    being whatever name the file imported ``mca_param`` under."""
    knobs = {}
    package = ROOT / "parsec_tpu"
    for path in sorted(package.rglob("*.py")):
        tree = ast.parse(path.read_text())
        aliases = {a.asname or a.name for node in ast.walk(tree)
                   if isinstance(node, ast.ImportFrom)
                   for a in node.names if a.name == "mca_param"}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "register"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in aliases):
                continue
            name = node.args[0]
            assert isinstance(name, ast.Constant), \
                f"{path}:{node.lineno}: a knob's name is not a literal"
            knobs.setdefault(name.value, []).append(
                f"{path.relative_to(package)}:{node.lineno}")
    return knobs


def _module_exists(name):
    parts = name.split(".")
    if (ROOT / parts[0]).is_dir():          # the repo's own: by file
        base = ROOT.joinpath(*parts)
        return base.with_suffix(".py").is_file() \
            or (base / "__main__.py").is_file()
    return importlib.util.find_spec(parts[0]) is not None


def test_every_quoted_command_names_something_that_exists():
    cells = {w["name"] for w in
             json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
    missing, seen = [], 0
    for doc in DOCUMENTS:
        text = " ".join((ROOT / doc).read_text().split())
        for m in re.finditer(
                r"\bpython3? (?:-m ([\w.]+)|([\w./-]+\.py))", text):
            seen += 1
            module, script = m.groups()
            if module and not _module_exists(module):
                missing.append(f"{doc}: python -m {module}")
            if script and script not in WRITTEN_BY_THE_READER \
                    and not (ROOT / script).is_file():
                missing.append(f"{doc}: python {script}")
        for cell in re.findall(r"--workload ([\w]+)", text):
            if cell not in cells:
                missing.append(f"{doc}: --workload {cell}")
    assert seen >= 20 and missing == []


def test_every_knob_of_the_readmes_tables_is_registered():
    """The ``| Param | Default | Effect |`` tables: a row's first cell
    names one knob or two; the rows of environment variables say
    ``env``."""
    registered = _registered()
    rows, table = [], False
    for line in (ROOT / "README.md").read_text().splitlines():
        if not line.startswith("|"):
            table = False
        elif line.replace(" ", "") == "|Param|Default|Effect|":
            table = True
        elif table and not line.startswith("|---"):
            rows.append(line.split("|")[1])
    knobs = [name for cell in rows if " env" not in cell
             for name in re.findall(r"`([^`]+)`", cell)]
    assert len(knobs) >= 16
    assert [k for k in knobs if k not in registered] == []


def test_the_registered_knobs_are_this_list():
    registered = _registered()
    assert sorted(registered) == KNOBS
