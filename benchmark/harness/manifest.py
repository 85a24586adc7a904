"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Paths hang off this file's own place, so a copy of ``BENCHMARK.json`` and
``benchmark/`` elsewhere resolves inside the copy. A later PR adds a
configuration, a cell or a per-layer metric by adding files and entries;
nothing here knows any of their names.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _check_name(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"{name!r} is not a name: a letter, digit or _ "
                         "first, then at most 63 letters, digits, _ . -")
    return name


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_manifest() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_entry(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                   f"{[c['name'] for c in manifest['workloads']]}")


def load_workload(name: str) -> dict:
    """``benchmark/workloads/<cell>.json``: the cell's traffic as data."""
    return _read_json(os.path.join(BENCH_DIR, "workloads",
                                   _check_name(name) + ".json"))


def load_config(manifest: dict, name: str) -> dict:
    """The configuration's file of sizes: at the path the manifest gives,
    or, for one that waits unlisted, ``benchmark/configs/<name>.json``."""
    for config in manifest["configs"]:
        if config["name"] == name:
            return _read_json(os.path.join(ROOT, config["file"]))
    return _read_json(os.path.join(BENCH_DIR, "configs",
                                   _check_name(name) + ".json"))


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, _check_name(name) + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_runner(name: str):
    """``benchmark/runners/<name>.py``: one per kind of traffic."""
    return _load_module("runners", name)


def load_metric(name: str):
    """``benchmark/metrics/<metric>.py``: one reader per per-layer metric."""
    return _load_module("metrics", name)


def load_reference(name: str):
    """``benchmark/reference/<family>.py``: the plain reference and the map
    from the published keys to the program's configuration."""
    return _load_module("reference", name)


def metrics_of(manifest: dict, group: str, cell: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: all
    without a ``workloads`` key, and those whose key lists the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]
