"""The benchmark finds every piece of a cell by name, and an added file
is picked up without an edit to a file that is already there."""
import hashlib
import json
import os
import re

import pytest

import chipbench_tiny
from chipbench import catalog

REPO = chipbench_tiny.REPO
BENCH = catalog.benchmark(REPO)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "-m"]
    assert BENCH["command"][2].split(".")[0] in BENCH["paths"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))


def test_metric_entries():
    names = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e
        assert callable(catalog.metric_reader(m["name"], REPO))


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_pieces_found_by_name(name):
    cell = catalog.workload(BENCH, name)
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    cfg = catalog.config(BENCH, cell["config"], REPO)
    entry = [c for c in BENCH["configs"] if c["name"] == cell["config"]][0]
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert cfg["name"] == cell["config"]
    for key in entry["reduced"]:
        assert key in cfg, f"reduced key {key} is not in {entry['file']}"
    tr = catalog.traffic(cell["traffic"], REPO)
    assert tr["mode"] in ("train", "infer")
    lim = catalog.limits(name, REPO)
    assert lim and all(v > 0 for v in lim.values())
    reported = [m["name"] for m in catalog.end_to_end(BENCH, name)]
    assert "setup_s" in reported and len(reported) >= 2
    assert catalog.per_layer(BENCH, name)


def test_unknown_names_are_errors():
    with pytest.raises(catalog.CatalogError):
        catalog.workload(BENCH, "no-such-cell")
    with pytest.raises(catalog.CatalogError):
        catalog.metric_reader("no_such_metric", REPO)
    with pytest.raises(catalog.CatalogError):
        catalog.traffic("no-such-traffic", REPO)


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_added_files_are_picked_up(tmp_path):
    root = chipbench_tiny.make_root(str(tmp_path))
    before = _digest(root)
    with open(os.path.join(root, "chipbench", "metrics", "new_metric.py"), "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    with open(os.path.join(root, "chipbench", "traffic", "new-mix.json"), "w") as f:
        json.dump(dict(chipbench_tiny.INFER, batch=1), f)
    with open(os.path.join(root, "chipbench", "limits", "new-cell.json"), "w") as f:
        json.dump(chipbench_tiny.LIMITS["tiny-infer"], f)
    bench = catalog.benchmark(root)
    bench["workloads"].append({"name": "new-cell", "config": "tiny-detr",
                               "traffic": "new-mix", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "Device", "moves": "images_per_s",
                               "workloads": ["new-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    bench = catalog.benchmark(root)
    assert catalog.traffic(catalog.workload(bench, "new-cell")["traffic"],
                           root)["batch"] == 1
    assert catalog.limits("new-cell", root)
    names = [m["name"] for m in catalog.per_layer(bench, "new-cell")]
    assert "new_metric" in names
    assert "new_metric" not in [m["name"] for m in catalog.per_layer(bench, "tiny-train")]
    assert catalog.metric_reader("new_metric", root)(None) == 42.0
    after = _digest(root)
    assert all(after[p] == h for p, h in before.items())
