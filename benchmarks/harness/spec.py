"""BENCHMARK.json and the files it names: cells, configurations, traffic
mixes, per-layer metric readers and model families are all found by name,
so a later PR adds a cell, or a model of another architecture, by adding
files and an entry, never by editing these."""

from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# where family modules are looked for, in this order (a test adds its own)
FAMILY_DIRS = [os.path.join(BENCH_DIR, "families")]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json; "
                     f"known: {[c['name'] for c in bench['workloads']]}")


def config_of(bench: dict, cell: dict, root: str = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == cell["config"]:
            return load_json(os.path.join(root, cfg["file"]))
    raise SystemExit(f"benchmark: cell {cell['name']!r} names configuration "
                     f"{cell['config']!r}, which BENCHMARK.json lacks")


def traffic_of(cell: dict) -> dict:
    """The cell's traffic mix: benchmarks/traffic/<traffic>.json."""
    return load_json(os.path.join(BENCH_DIR, "traffic",
                                  cell["traffic"] + ".json"))


def metrics_for(bench: dict, group: str, cell_name: str) -> list:
    """The entries of `end_to_end` or `per_layer` this cell reports: those
    with no `workloads` key, or with the cell in it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


@functools.lru_cache(maxsize=None)
def _module_at(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_of(config: dict):
    """The module that knows this configuration's model:
    benchmarks/families/<model_type>.py (its interface is the docstring of
    benchmarks/families/__init__.py). Loaded by file path, once a process,
    so that its jitted makers are made once."""
    name = config.get("model_type")
    if not name:
        raise SystemExit("benchmark: the configuration file has no "
                         "\"model_type\", so no family module can be found")
    tried = [os.path.join(d, name + ".py") for d in FAMILY_DIRS]
    for path in tried:
        if os.path.exists(path):
            return _module_at(path, "bench_family_" + "".join(
                c if c.isalnum() else "_" for c in name))
    raise SystemExit(f"benchmark: no family module for model_type {name!r}: "
                     f"looked for {' and '.join(tried)}")


def layer_reader(name: str):
    """The reader of one per-layer metric:
    benchmarks/layer_metrics/<name>.py, a module with `read(facts)` that
    returns a number, or None where it finds nothing to read. A quantity
    split by the end-to-end metric it moves (`device_idle_share.train`,
    `device_idle_share.serve`) has one reader, named without the part
    after the last dot, unless a file of the whole name is there."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH_DIR, "layer_metrics",
                            name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
