"""BENCHMARK.json and the files its entries name.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: every lookup goes from a name in BENCHMARK.json to a file under
``benchmark/``.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
#: Generated at run time (tokenizer, plans, traces); listed in .gitignore.
RUN_DIR = os.path.join(ROOT, ".bench_run")


class ManifestError(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_named(entries: list[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise ManifestError(f"no {what} named {name!r} in BENCHMARK.json "
                        f"(known: {[e['name'] for e in entries]})")


def cell_files(manifest: dict, cell_name: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration, traffic and (optional) cell
    parameters loaded: what one pair of configuration and mix needs (a
    rate, say) lives in ``benchmark/cells/<cell>.json``."""
    cell = find_named(manifest["workloads"], cell_name, "workload")
    config_entry = find_named(manifest["configs"], cell["config"], "config")
    traffic = load_json(os.path.join(
        root, "benchmark", "traffic", cell["traffic"] + ".json"))
    cell_path = os.path.join(root, "benchmark", "cells", cell_name + ".json")
    params = dict(traffic.get("params", {}))
    if os.path.exists(cell_path):
        params.update(load_json(cell_path).get("params", {}))
    return {"cell": cell, "config_entry": config_entry,
            "config": load_json(os.path.join(root, config_entry["file"])),
            "generator": traffic["generator"], "params": params}


def metrics_of(manifest: dict, kind: str, cell_name: str) -> list[dict]:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports: a
    metric without a ``workloads`` key belongs to every cell."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(subdir: str, name: str, root: str = ROOT):
    """``benchmark/<subdir>/<name>.py`` as a module, found by name."""
    path = os.path.join(root, "benchmark", subdir, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"benchmark/{subdir}/{name}.py does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{subdir}_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config_module(config: dict, key: str, default, needs: tuple,
                  root: str = ROOT):
    """What a configuration's optional ``key`` ("reference", "roofline")
    names: ``benchmark/<key>s/<name>.py``, which has to expose ``needs``;
    without the key, ``default``, the module of lib/ that holds the dense
    block's. Returns the module and where it came from."""
    name = config.get(key)
    if name is None:
        return default, f"lib/{key}.py"
    module = load_module(key + "s", name, root)
    missing = [n for n in needs if not callable(getattr(module, n, None))]
    if missing:
        raise ManifestError(
            f"benchmark/{key}s/{name}.py does not define {missing}")
    return module, f"{key}s/{name}.py"
