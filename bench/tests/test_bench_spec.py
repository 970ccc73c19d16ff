"""The harness finds every part of a cell by its name in BENCHMARK.json,
and the file keeps to the benchmark's contract."""
import json
import os
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench_json():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files(bench_json):
    for w in bench_json["workloads"]:
        cell = spec.load_cell(w["name"])
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m.moves in names, (w["name"], m.name)
            assert callable(m.reader)
        assert cell.chips in (1, 4)
        assert cell.config["chips"] == cell.chips
        assert set(cell.traffic["limits"]) == {"dist_gap", "bad_rows",
                                               "missing"}


def test_contract_names_and_shapes(bench_json):
    assert set(bench_json) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench_json["run_seconds"] <= 51
    named = (bench_json["configs"] + bench_json["workloads"]
             + bench_json["end_to_end"] + bench_json["per_layer"])
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for c in bench_json["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            assert set(c["reduced"]) == set(json.load(f)["reduced"])
    e2e = {m["name"]: m for m in bench_json["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in bench_json["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], []).append(m["name"])
    cells = [w["name"] for w in bench_json["workloads"]]
    assert len(set(cells)) == len(cells)
    assert sum(w["chips"] == 4 for w in bench_json["workloads"]) \
        <= max(1, len(cells) // 2)


def _copy_tree(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds a cell as new files plus entries in
    BENCHMARK.json; no file the harness already has is edited."""
    root = _copy_tree(tmp_path)
    with open(root / "bench" / "configs" / "box-4M.json") as f:
        cfg = json.load(f)
    cfg["points"] = 1 << 16
    (root / "bench" / "configs" / "box-64k.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "knn-small.json").write_text(json.dumps({
        "kind": "knn", "loop": "closed", "clients": 1, "rows": 64,
        "limits": {"dist_gap": 1e-6, "bad_rows": 0, "missing": 0}}))
    (root / "bench" / "layer_metrics" / "rows_served.py").write_text(
        "def read(run):\n    return 7.0\n")
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    b["workloads"].append({"name": "box-64k.knn-small", "config": "box-64k",
                           "traffic": "knn-small", "chips": 1, "why": "x"})
    next(m for m in b["end_to_end"] if m["name"] == "qps")[
        "workloads"].append("box-64k.knn-small")
    b["per_layer"].append({"name": "rows_served", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "service", "moves": "qps",
                           "workloads": ["box-64k.knn-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("box-64k.knn-small", root=str(root))
    assert cell.config["points"] == 1 << 16
    assert cell.traffic["rows"] == 64
    assert [m.name for m in cell.end_to_end] == ["setup_s", "qps"]
    assert [m.name for m in cell.per_layer] == ["rows_served"]
    assert cell.per_layer[0].reader(None) == 7.0
    # the cells that were there resolve as before
    old = spec.load_cell("box-16M-4chip.within-bulk", root=str(root))
    assert "rows_served" not in [m.name for m in old.per_layer]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")


def test_sweep_and_proof_find_a_cell_not_listed_yet(tmp_path):
    root = _copy_tree(tmp_path)
    (root / "bench" / "traffic" / "knn-small.json").write_text(json.dumps({
        "kind": "knn", "loop": "closed", "clients": 1, "rows": 64,
        "limits": {"dist_gap": 1e-6, "bad_rows": 0, "missing": 0}}))
    with pytest.raises(KeyError):
        spec.load_cell("box-4M.knn-small", root=str(root))
    cell = spec.find_cell("box-4M.knn-small", root=str(root))
    assert cell.chips == 1 and cell.traffic["rows"] == 64
    assert cell.end_to_end == () and cell.per_layer == ()
    with pytest.raises(KeyError):
        spec.find_cell("nodot", root=str(root))
