"""A new configuration, traffic mix, cell and per-layer metric are new
files only: the harness finds each by its name, and no file that the
benchmark already has changes."""
import hashlib
import json
import os

import benchtest_util as util
import harness


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" not in d:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.relpath(os.path.join(d, f), root)] = \
                        hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    root = util.copy_bench(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = digest(bench)

    config = harness.load_json(os.path.join(bench, "configs",
                                            "sedov_t2.json"))
    config["hydro"]["levels"] = 2
    config["density_noise"] = 0.0
    with open(os.path.join(bench, "configs", "sedov_l2.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "s3_new.json"), "w") as f:
        json.dump({"strategy": "s3"}, f)
    with open(os.path.join(bench, "metrics", "steps_done.py"), "w") as f:
        f.write("def read(run):\n    return float(run.steps)\n")

    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({"name": "sedov_l2", "source": "test",
                            "file": "bench/configs/sedov_l2.json",
                            "reduced": ["levels"], "why": "test"})
    spec["workloads"].append({"name": "sedov_l2.s3_new",
                              "config": "sedov_l2", "traffic": "s3_new",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_done", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "entry", "moves": "step_ms",
                              "workloads": ["sedov_l2.s3_new"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.load_cell(root, "sedov_l2.s3_new")
    assert cell.config["hydro"]["levels"] == 2
    assert cell.config["density_noise"] == 0.0
    assert cell.traffic == {"strategy": "s3"}
    assert [m["name"] for m in cell.per_layer] == ["steps_done"]
    assert [m["name"] for m in cell.end_to_end] == ["step_ms", "setup_s"]
    assert cell.module("reference").step_work(
        cell.config, harness.load_json(
            os.path.join(bench, "counts", "hydro_rhs.json")))[0] \
        == 3 * 64 * 17516040
    run = harness.Run(cell=cell, device_kind="TPU v5 lite", steps=7,
                      window_s=1.0, setup_s=2.0)
    assert harness.read_metrics(run, cell.per_layer) == {
        "steps_done": {"value": 7.0, "unit": "steps"}}
    assert harness.read_metrics(run, cell.end_to_end) == {
        "step_ms": {"value": 1e3 / 7, "unit": "ms"},
        "setup_s": {"value": 2.0, "unit": "s"}}

    after = digest(bench)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/sedov_l2.json", "traffic/s3_new.json",
        "metrics/steps_done.py"}


def test_unknown_cell_is_refused():
    try:
        harness.load_cell(util.REPO, "no_such.cell")
    except SystemExit as err:
        assert "no_such.cell" in str(err)
    else:
        raise AssertionError("an unknown cell must stop the run")
