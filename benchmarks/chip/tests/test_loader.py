"""The harness finds every cell's configuration, traffic, driver, limits
and per-layer metric readers by name; BENCHMARK.json keeps to the shape
the benchmark contract asks for.  A new cell is new files plus its entry."""
import json
import os
import re
import shutil

import pytest

from bench import loader

BENCH = loader.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_resolves_every_file_by_name(workload):
    cell = loader.resolve(workload)
    assert os.path.isfile(cell.driver_path)
    assert hasattr(cell.driver(), "Driver")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert hasattr(cell.metric_reader(m["name"]), "read")
    assert cell.limits and all(v > 0 for v in cell.limits.values())


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(loader.ROOT, c["file"]))
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_a_new_cell_needs_only_new_files(tmp_path):
    """Copy the benchmark, add a traffic file, a limits file and a
    BENCHMARK.json entry: the new cell resolves with no code edited."""
    root = tmp_path / "checkout"
    shutil.copytree(loader.HERE, root / BENCH["paths"][0],
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads(json.dumps(BENCH))
    chip = root / BENCH["paths"][0]
    tf = json.loads((chip / "traffic" / "gen-closed-1024x64.json").read_text())
    tf["group_batches"] = 2
    (chip / "traffic" / "gen-closed-1024x64-g2.json").write_text(
        json.dumps(tf))
    (chip / "limits" / "teacher-g2.json").write_text(
        (chip / "limits" / "teacher-gen.json").read_text())
    bench["workloads"].append({"name": "teacher-g2",
                               "config": "lstm-am-teacher",
                               "traffic": "gen-closed-1024x64-g2",
                               "chips": 1, "why": "two batches a call"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "teacher-gen" in m.get("workloads", ()):
            m["workloads"].append("teacher-g2")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = loader.resolve("teacher-g2", root=str(root))
    assert cell.traffic["group_batches"] == 2
    assert cell.driver_path.startswith(str(root))
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in loader.resolve("teacher-gen").per_layer}
