"""Finds everything the harness runs by the name in ``BENCHMARK.json``.

A cell, a configuration, a driver, a per-layer metric and its reader each
sit in a file of their own under ``<root>/benchmark/``; adding one is a
new file plus a new entry, never an edit of a file that is there:

    BENCHMARK.json                    cells, metrics, bounds (the contract)
    benchmark/workloads/<cell>.json   traffic parameters of one cell
    benchmark/configs/<config>.json   one configuration, names its driver
    benchmark/configs/<ref>.py        the configuration's plain reference
    benchmark/drivers/<driver>.py     build(...) -> the object run.py steps
    benchmark/metrics/<metric>.json   one per-layer metric, names its reader
    benchmark/readers/<reader>.py     read(record, params) -> number | None

``root`` is the checkout (the directory that holds ``BENCHMARK.json``);
tests point it at a temporary copy.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(ValueError):
    """A name that resolves to nothing, or files that disagree."""


def _check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(f"{kind} name {name!r} is not [A-Za-z0-9_.-]+")
    return name


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise ManifestError(f"{path} does not exist") from None
    except json.JSONDecodeError as exc:
        raise ManifestError(f"{path}: {exc}") from None


class Manifest:
    """``BENCHMARK.json`` plus the files its names point at."""

    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        self.dir = os.path.join(root, "benchmark")
        self.bench = _load_json(os.path.join(root, "BENCHMARK.json"))

    # -- BENCHMARK.json entries -------------------------------------------
    def _entry(self, section: str, name: str) -> Dict[str, Any]:
        for e in self.bench[section]:
            if e["name"] == name:
                return e
        known = [e["name"] for e in self.bench[section]]
        raise ManifestError(
            f"{name!r} is not in BENCHMARK.json {section}: {known}")

    def cell(self, name: str) -> Dict[str, Any]:
        return self._entry("workloads", _check_name("workload", name))

    def metrics_for(self, section: str, cell: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` entries that apply to
        ``cell`` (an entry without ``workloads`` applies to every cell)."""
        return [m for m in self.bench[section]
                if cell in m.get("workloads", [cell])]

    # -- data files -------------------------------------------------------
    def workload(self, cell: str) -> Dict[str, Any]:
        """``workloads/<cell>.json``; it must agree with the cell's entry
        in ``BENCHMARK.json`` on configuration and chips."""
        entry = self.cell(cell)
        wl = _load_json(os.path.join(self.dir, "workloads", cell + ".json"))
        for key in ("config", "chips"):
            if wl.get(key) != entry[key]:
                raise ManifestError(
                    f"workloads/{cell}.json says {key}={wl.get(key)!r}, "
                    f"BENCHMARK.json says {entry[key]!r}")
        return wl

    def config(self, name: str) -> Dict[str, Any]:
        entry = self._entry("configs", _check_name("config", name))
        return _load_json(os.path.join(self.root, entry["file"]))

    def metric(self, name: str) -> Dict[str, Any]:
        _check_name("metric", name)
        return _load_json(os.path.join(self.dir, "metrics", name + ".json"))

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        """The published peaks of ``device_kind``. A device that is not
        in the table is an error, never a default."""
        table = _load_json(os.path.join(self.dir, "peaks.json"))
        if device_kind not in table:
            raise ManifestError(
                f"no peaks for device kind {device_kind!r} in "
                f"benchmark/peaks.json (known: {sorted(table)})")
        return table[device_kind]

    # -- code files, loaded by path so that a new one needs no edit -------
    def _module(self, sub: str, name: str):
        _check_name(sub, name)
        path = os.path.join(self.dir, sub, name + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"{path} does not exist")
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{sub}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def driver(self, name: str):
        mod = self._module("drivers", name)
        if not callable(getattr(mod, "build", None)):
            raise ManifestError(f"drivers/{name}.py defines no build()")
        return mod

    def reader(self, name: str):
        mod = self._module("readers", name)
        if not callable(getattr(mod, "read", None)):
            raise ManifestError(f"readers/{name}.py defines no read()")
        return mod

    def reference(self, name: str):
        return self._module("configs", name)
