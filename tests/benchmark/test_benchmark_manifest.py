"""BENCHMARK.json and every file it names load and cross-reference, and
a cell, a configuration, a per-layer metric and a runner can each be
added by new files plus one entry — shown in a temporary copy."""

import json
import os
import re
import shutil

import pytest

import bench_tiny_root
from benchmark import harness

REPO = bench_tiny_root.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = harness.load_json("BENCHMARK.json", root=REPO)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])


def test_names_units_and_entry_keys():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(MANIFEST["paths"]))
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("workload", CELLS)
def test_every_file_a_cell_names_loads(workload):
    cell = harness.load_cell(workload, REPO)
    assert cell["cell"]["name"] == workload
    for key in ("config", "traffic", "chips", "why"):
        assert cell["cell"][key] == cell["entry"][key]
    assert cell["config"]["name"] == cell["entry"]["config"]
    assert cell["config"]["item"] in ("image", "token")
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == cell["entry"]["config"])
    assert entry["reduced"] == cell["config"]["reduced"]
    assert entry["source"] == cell["config"]["source"]
    runner = harness.load_runner(cell)
    assert callable(runner.program) and callable(runner.reference)
    ref = harness.load_reference(cell)
    assert ref.param_spec(cell["config"], cell["cell"]["section"])
    for m in cell["per_layer"]:
        assert callable(harness.load_reader(cell, m["reader"]).read)
    # every cell reports setup_s, another end-to-end metric and a layer metric
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell["per_layer"]
    limits = cell["cell"]["limits"]
    assert limits and all(isinstance(v, (int, float))
                          for v in limits.values())


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_each_layer_metric_moves_a_metric_its_cells_report(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    target = next(e for e in MANIFEST["end_to_end"]
                  if e["name"] == m["moves"])
    for workload in m.get("workloads", CELLS):
        assert workload in CELLS
        assert "workloads" not in target or workload in target["workloads"]
    spec = harness.load_json("benchmark", "layer_metrics", f"{metric}.json",
                             root=REPO)
    assert spec["name"] == metric and spec["what"]


def test_files_under_paths_use_only_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        for d, _, files in os.walk(os.path.join(REPO, path)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), REPO)), f


def test_peaks_table_is_keyed_by_device_kind_with_a_source():
    table = harness.load_json("benchmark", "peaks.json", root=REPO)
    assert table["source"]
    assert table["by_device_kind"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v99", REPO)


def test_a_cell_config_metric_and_runner_are_added_by_new_files(tmp_path):
    """New files plus one entry each in BENCHMARK.json; no file that is
    there is edited."""
    root = os.path.join(tmp_path, "root")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")
    before = {}
    for d, _, files in os.walk(b):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    def write(rel, obj):
        with open(os.path.join(b, rel), "w") as f:
            if isinstance(obj, str):
                f.write(obj)
            else:
                json.dump(obj, f)

    cfg = harness.load_json("benchmark", "configs", "gpt2-xl.json", root=REPO)
    cfg.update(name="gpt2-large", n_embd=1280, n_layer=36, n_head=20,
               source="https://huggingface.co/openai-community/gpt2-large")
    write("configs/gpt2-large.json", cfg)
    shutil.copy(os.path.join(b, "reference", "gpt2-xl.py"),
                os.path.join(b, "reference", "gpt2-large.py"))
    traffic = harness.load_json("benchmark", "traffic", "serve-closed16.json",
                                root=REPO)
    traffic.update(arrival="open", rate=4.0, process="bursty", burst_every=20,
                   burst_len=5, burst_factor=4.0)
    write("traffic/serve-open4.json", traffic)
    write("workloads/gpt2-large.serve-open4.json", {
        "name": "gpt2-large.serve-open4", "config": "gpt2-large",
        "traffic": "serve-open4", "runner": "echo", "section": "serve",
        "chips": 1, "why": "a later PR's cell", "limits": {"x": 0},
        "program": {"serve": {"slots": 8}}})
    write("runners/echo.py", "def program(ctx):\n    return {}\n\n\n"
          "def reference(ctx, prog):\n    return {'correct': True}\n")
    write("layer_metrics/readers/const.py",
          "def read(obs, params):\n    return params['value']\n")
    write("layer_metrics/lateness_ms.serve.json", {
        "name": "lateness_ms.serve", "reader": "const",
        "params": {"value": 1.5}, "what": "a later PR's metric"})
    man = json.loads(json.dumps(MANIFEST))
    man["configs"].append({
        "name": "gpt2-large", "source": cfg["source"],
        "file": "benchmark/configs/gpt2-large.json",
        "reduced": cfg["reduced"], "why": "a later PR's configuration"})
    man["workloads"].append({
        "name": "gpt2-large.serve-open4", "config": "gpt2-large",
        "traffic": "serve-open4", "chips": 1, "why": "a later PR's cell"})
    for m in man["end_to_end"]:
        if "workloads" in m and m["name"] != "train_items_per_s":
            m["workloads"].append("gpt2-large.serve-open4")
    man["per_layer"].append({
        "name": "lateness_ms.serve", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "load generator",
        "moves": "itl_p95_ms", "workloads": ["gpt2-large.serve-open4"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)

    cell = harness.load_cell("gpt2-large.serve-open4", root)
    assert cell["config"]["n_embd"] == 1280
    assert cell["traffic"]["arrival"] == "open"
    assert harness.load_runner(cell).reference({}, {}) == {"correct": True}
    assert harness.load_reference(cell).sizes(cell["config"],
                                              "serve")["n_layer"] == 36
    from benchmark.runners_common import read_layer_metrics
    assert read_layer_metrics(cell, {"host": {}}) == {
        "lateness_ms.serve": 1.5}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tok_per_s", "itl_p95_ms", "setup_s"}
    # the old cells are untouched, and no existing file was edited
    assert harness.load_cell(CELLS[0], root)["cell"]["name"] == CELLS[0]
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path
