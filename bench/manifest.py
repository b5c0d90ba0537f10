"""`BENCHMARK.json` and the files it names.

Each configuration, traffic mix and metric lives in a file of its own,
found by its name: `configs/<config>.json`, `traffic/<traffic>.json` and
`metrics/<metric>.py`. A cell added later brings its own files and edits
none of these.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in manifest['workloads']]}")


def metrics(manifest: dict, workload_name: str, trace: bool) -> list[dict]:
    """The end-to-end metrics of a cell (or, traced, its per-layer ones):
    those without a `workloads` key and those that list the cell."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if workload_name in m.get("workloads", [workload_name])]


def reader(metric_name: str):
    """The `read(run)` function of `metrics/<metric_name>.py`."""
    path = os.path.join(BENCH, "metrics", f"{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
