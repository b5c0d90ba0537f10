"""BENCHMARK.json: every cell finds its files by name, every name and unit
keeps to the allowed characters, and the harness gives no result off the
chip."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import load, manifest  # noqa: E402

MAN = manifest.load(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
METRIC_KEYS = {"end_to_end": {"name", "unit", "better", "bound", "source"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves"}}


def test_top_level_keys_and_paths():
    assert set(MAN) == KEYS
    assert MAN["command"][:2] == ["python3", "bench/run.py"]
    for p in MAN["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= MAN["run_seconds"] <= 51


def test_names_units_and_lines():
    names = [c["name"] for c in MAN["configs"]]
    cells = [w["name"] for w in MAN["workloads"]]
    metrics = [m["name"] for g in ("end_to_end", "per_layer")
               for m in MAN[g]]
    for group in (names, cells, metrics):
        assert len(set(group)) == len(group)
        assert all(NAME.match(n) for n in group), group
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert LINE.match(c["why"]) and LINE.match(c["source"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert LINE.match(w["why"])
    for group, keys in METRIC_KEYS.items():
        for m in MAN[group]:
            assert set(m) - {"workloads"} == keys, m["name"]
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                              "higher")
            assert set(m.get("workloads", cells)) <= set(cells)
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert LINE.match(m["layer"])
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}
    assert len(json.dumps(MAN)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_cell_resolves_its_files(cell):
    w = manifest.workload(MAN, cell)
    entry = next(c for c in MAN["configs"] if c["name"] == w["config"])
    assert entry["file"] == f"bench/configs/{w['config']}.json"
    cfg = manifest.config(w["config"])
    assert cfg["name"] == w["config"] and cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"]
    assert all(v is not None for v in cfg["limits"].values())
    traffic = manifest.traffic(w["traffic"])
    assert traffic["kind"] in load.GENERATORS
    e2e = manifest.metrics(MAN, cell, trace=False)
    layer = manifest.metrics(MAN, cell, trace=True)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
        assert callable(manifest.reader(m["name"]))
    for m in layer:
        assert m["moves"] in [e["name"] for e in e2e]


def test_run_gives_no_result_without_a_tpu():
    cell = MAN["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
