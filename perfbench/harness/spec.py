"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; each
lives in a file of its own under ``perfbench/``:

  configuration   the file its ``configs`` entry names (JSON), with the
                  plain reference module that the file's ``reference``
                  key names, beside it in ``configs/``
  traffic mix     ``traffic/<traffic>.json``
  per-layer metric  ``metrics/<name>.py``, a module with ``read(w)``

A later cell is added with new files and entries alone.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = _named(bench["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(name: str, directory: str = None) -> dict:
    with open(os.path.join(directory or os.path.join(HERE, "traffic"),
                           f"{name}.json")) as f:
        return json.load(f)


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict) -> ModuleType:
    """The configuration's plain reference (``configs/<reference>``)."""
    name = cfg["reference"]
    return _module(os.path.join(HERE, "configs", name),
                   f"perfbench_ref_{os.path.splitext(name)[0]}")


def metric_reader(name: str) -> ModuleType:
    return _module(os.path.join(HERE, "metrics", f"{name}.py"),
                   f"perfbench_metric_{name.replace('.', '_')}")


def per_layer_for(bench: dict, cell: str) -> list:
    """The per-layer metrics this cell reports."""
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def end_to_end_for(bench: dict, cell: str) -> set:
    """The names of the end-to-end metrics this cell reports."""
    return {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
