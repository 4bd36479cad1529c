"""A configuration, a traffic mix and a per-layer metric are added as new
files and one ``workloads`` entry, and the harness finds each by name
with no edit to any file that was there."""

import hashlib
import json
import shutil
import time

from bench import harness

from bench_smoke import smoke_model


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                     ).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    src = harness.ROOT
    shutil.copytree(src / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "bench")

    conf = json.loads((src / "bench/configs/olmo-1b.json").read_text())
    conf.update(name="olmo-small", max_context=1024,
                model=smoke_model("olmo-1b", conf["model"]), smoke=True)
    conf["limits"] = {k: 10.0 for k in conf["limits"]}
    (tmp_path / "bench/configs/olmo-small.json").write_text(json.dumps(conf))
    traffic = json.loads(
        (src / "bench/traffic/batch_map_32.json").read_text())
    traffic.update(rows_per_plan=2, row_bytes={
        "dist": "uniform", "min": 30, "max": 50})
    (tmp_path / "bench/traffic/two_rows.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/rows_seen.py").write_text(
        "def read(rec):\n    return rec['rows']\n")

    spec = json.loads((src / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "olmo-small", "source": "test",
                            "file": "bench/configs/olmo-small.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "olmo-small.two_rows",
                              "config": "olmo-small",
                              "traffic": "two_rows", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "rows_seen", "unit": "rows",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "rows_per_s",
                              "workloads": ["olmo-small.two_rows"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(harness.load_spec(tmp_path),
                             "olmo-small.two_rows", tmp_path)
    assert cell["config"]["name"] == "olmo-small"
    assert cell["traffic"]["rows_per_plan"] == 2
    assert "rows_seen" in [m["name"] for m in cell["per_layer"]]

    res = harness.run(cell, 2**31 + 5, 0.05, True, time.perf_counter(),
                      harness.load_peaks(tmp_path))
    assert res["correct"], res["checks"]
    assert res["metrics"]["rows_seen"] == {"value": 2.0 * res["attempted"],
                                           "unit": "rows"}
    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/olmo-small.json", "traffic/two_rows.json",
        "metrics/rows_seen.py"}
