"""The layers of ``parsec_tpu``, written once, and the imports that break
them, by name.

A subpackage may import from its own layer and from the layers below it.
The walk reads every ``import`` / ``from … import`` of every module with
``ast``, the lazy ones inside functions included, so nothing has to be
imported to be judged. ``parsec_tpu/__init__.py`` imports everything and
is not a layer.

``ALLOWED_UPWARD`` holds exactly the imports that point UP today, each
with the number of statements that make it and the ROADMAP debt that
undoes it. The table may only shrink: an entry added to it, or a count
raised, is a review finding, not a fix. A PR that removes an upward
import takes its entry out (or lowers its count) in the same change.

The second test is the arrow between the program and what measures it:
the package ships no benchmark of its own beyond the three harnesses the
multi-rank tests use as fixtures, and imports nothing that measures it.
"""

import ast
import functools
import pathlib

import pytest

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "parsec_tpu"

# lowest first; members of one tuple may import one another
LAYERS = (
    ("version", "utils"),
    ("ops", "termdet", "_native"),
    ("core",),
    ("data", "sched", "device", "profiling"),
    ("dsl", "comm"),
    ("compiled", "algorithms"),
    ("analysis",),
    ("serving",),
)
RANK = {name: rank for rank, layer in enumerate(LAYERS) for name in layer}

# (from, to): (import statements, the debt that removes them)
ALLOWED_UPWARD = {
    # Context and Taskpool look their observers and services up
    ("core", "data"): (1, "ROADMAP D11: Context.checkpoint"),
    ("core", "sched"): (1, "ROADMAP D11: Context.__init__"),
    ("core", "device"): (4, "ROADMAP D11: Context.__init__, "
                            "_merge_region, native_exec's HBM tracking"),
    ("core", "profiling"): (4, "ROADMAP D11: the trace, the metrics "
                               "registry, SDE"),
    ("core", "analysis"): (1, "ROADMAP D11: Taskpool.validate -> lint"),
    ("core", "serving"): (1, "ROADMAP D11: Context.submit"),
    # the data layer builds taskpools and models of its own
    ("data", "dsl"): (4, "ROADMAP D11: matrix_ops, redistribute and "
                         "recovery build PTG/DTD pools"),
    ("data", "comm"): (1, "ROADMAP D11: matrix_ops' broadcast trees"),
    ("data", "analysis"): (1, "ROADMAP D11: recovery's lineage model"),
    # the old device-to-device plane behind comm.device_direct
    ("device", "comm"): (1, "ROADMAP D2: comm.device_direct"),
    ("device", "compiled"): (1, "ROADMAP D2: comm_mesh_device"),
    ("comm", "compiled"): (2, "ROADMAP D2: device_plane's comm mesh; "
                              "D3: pingpong's ICI row (B3's cell)"),
    # tools that live with the observers they once served
    ("profiling", "dsl"): (3, "ROADMAP D15: sim, ptg_to_dtd"),
    ("profiling", "analysis"): (1, "ROADMAP D15: the dfsan PINS module"),
}

# the multi-rank tests' fixtures (ROADMAP D3): each goes when the cell
# that takes it is written
FIXTURE_HARNESSES = {"comm/bcast_bench", "comm/recovery_bench",
                     "serving/serving_bench"}
MEASURING = {"benchmark", "bench", "chip_smoke"}


def _modules():
    return sorted(p for p in PACKAGE.rglob("*.py")
                  if "__pycache__" not in p.parts)


@functools.lru_cache(maxsize=None)
def _imported(path):
    """Every module a file imports, absolute: ``(dotted name parts,
    names imported from it, line)`` per statement."""
    rel = path.relative_to(PACKAGE.parent).with_suffix("").parts
    package = rel[:-1]          # the package a relative import starts at
    found = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found += [(tuple(alias.name.split(".")), (), node.lineno)
                      for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - (node.level - 1)] \
                if node.level else ()
            module = base + tuple(node.module.split(".")
                                  if node.module else ())
            found.append((module, tuple(a.name for a in node.names),
                          node.lineno))
    return found


@functools.lru_cache(maxsize=None)
def _edges():
    """``{(from, to): [where, ...]}`` between subpackages, one entry a
    statement."""
    edges = {}
    for path in _modules():
        rel = path.relative_to(PACKAGE)
        if rel.parts == ("__init__.py",):
            continue
        src = rel.parts[0].removesuffix(".py")
        for module, names, line in _imported(path):
            if module[:1] != ("parsec_tpu",):
                continue
            # ``from .. import a, b`` names subpackages directly
            targets = {module[1]} if len(module) > 1 else set(names)
            for dst in targets - {src}:
                edges.setdefault((src, dst), []).append(f"{rel}:{line}")
    return edges


def test_the_layers_name_every_subpackage_once():
    found = {p.name.removesuffix(".py") for p in PACKAGE.iterdir()
             if p.name not in ("__init__.py", "__pycache__")
             and (p.is_dir() or p.suffix == ".py")}
    assert found == set(RANK)
    assert sum(map(len, LAYERS)) == len(RANK)
    assert all(src in RANK and dst in RANK for src, dst in ALLOWED_UPWARD)
    assert all(RANK[dst] > RANK[src] for src, dst in ALLOWED_UPWARD)


@pytest.mark.parametrize("subpackage", sorted(RANK, key=RANK.get))
def test_imports_point_sideways_or_down_or_are_named(subpackage):
    upward = {edge: len(where) for edge, where in _edges().items()
              if edge[0] == subpackage and RANK[edge[1]] > RANK[subpackage]}
    allowed = {edge: count for edge, (count, _) in ALLOWED_UPWARD.items()
               if edge[0] == subpackage}
    where = {edge: _edges()[edge] for edge in upward
             if upward[edge] != allowed.get(edge)}
    assert upward == allowed, (
        f"{subpackage}: imports that point up and the table disagree "
        f"(a new one is a review finding; a removed one leaves the "
        f"table in the same change): {where}")


def test_the_package_ships_no_benchmark_and_imports_none():
    harnesses = {str(p.relative_to(PACKAGE).with_suffix(""))
                 for p in _modules() if p.stem.endswith("_bench")}
    assert harnesses == FIXTURE_HARNESSES
    measured_by = [f"{p.relative_to(PACKAGE)}:{line} imports {module[0]}"
                   for p in _modules()
                   for module, _, line in _imported(p)
                   if module[:1] and module[0] in MEASURING]
    assert measured_by == []
