"""A configuration, a traffic mix and a per-layer metric dropped in as
new files, plus one BENCHMARK.json entry each: the harness finds them
by name and no file that was there is edited."""

import json
import os
import shutil

import pytest

from harness import spec


@pytest.fixture
def tree(tmp_path):
    bench = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "layer_metrics", "harness"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, d), bench / d)
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    return tmp_path, bench, doc


def add_cell(tmp_path, bench, doc):
    cfg = bench / "configs" / "new-model"
    cfg.mkdir()
    (cfg / "config.json").write_text(json.dumps({"vocab_size": 64}))
    (cfg / "cell.json").write_text(json.dumps(
        {"source": "a paper", "server_args": {}, "shape": {}}))
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "prompt_classes": [],
         "multiset": []}))
    (bench / "layer_metrics" / "new_metric.py").write_text(
        'METRICS = [{"name": "new_metric", "unit": "count", '
        '"layer": "a new layer", "moves": "out_tok_s", '
        '"source": "program_counter"}]\n'
        'def read(run):\n    return {"new_metric": run["answer"]}\n')
    doc["configs"].append({"name": "new-model", "source": "a paper",
                           "file": "benchmarks/configs/new-model/config.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "new.cell", "config": "new-model",
                             "traffic": "new-mix", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "new_metric", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "a new layer", "moves": "out_tok_s",
                             "workloads": ["new.cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_added_files_are_found_by_name(tree):
    tmp_path, bench, doc = tree
    path = add_cell(tmp_path, bench, doc)
    cell = spec.Cell("new.cell", str(bench), path)
    assert cell.model_config == {"vocab_size": 64}
    assert cell.traffic["clients"] == 1
    assert "new_metric" in cell.names("per_layer")
    # the metrics with no `workloads` key came along unasked
    assert "out_tok_s" in cell.names("end_to_end")
    assert "ttft_mean_ms" not in cell.names("end_to_end")
    found = spec.discover_layer_metrics(str(bench))
    got = spec.read_layer_metrics(
        cell, {"answer": 42, "turnarounds": [], "t0": 0, "t1": 1,
               "healthy_s": 1.0, "warmup_s": 2.0, "steps": [],
               "metrics_0": {}, "metrics_1": {}, "metrics_2": {},
               "health": {}}, found)
    assert got["new_metric"] == {"value": 42.0, "unit": "count"}
    assert got["healthy_s"]["value"] == 1.0
    # a reader with nothing to read leaves its metric out of the line
    assert "decode_step_ms" not in got and "peak_hbm_gib" not in got
    # an old cell is untouched by the addition
    old = spec.Cell("mistral7b.chat-closed", str(bench), path)
    assert "new_metric" not in old.names("per_layer")


def test_a_layer_metric_must_move_a_metric_the_cell_reports(tree):
    tmp_path, bench, doc = tree
    path = add_cell(tmp_path, bench, doc)
    doc = json.loads(open(path).read())
    doc["per_layer"][-1]["moves"] = "ttft_mean_ms"   # new.cell has none
    open(path, "w").write(json.dumps(doc))
    with pytest.raises(spec.SpecError, match="ttft_mean_ms"):
        spec.Cell("new.cell", str(bench), path)


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError):
        spec.Cell("no.such.cell")


def test_benchmark_json_agrees_with_the_metric_files():
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    found = spec.discover_layer_metrics()
    for m in doc["per_layer"]:
        decl, _read = found[m["name"]]
        for key in ("unit", "layer", "moves", "source"):
            assert decl[key] == m[key], (m["name"], key)
    for w in doc["workloads"]:
        cell = spec.Cell(w["name"])
        assert "setup_s" in cell.names("end_to_end")
        assert len(cell.names("end_to_end")) >= 2 and cell.per_layer
        assert os.path.isfile(os.path.join(
            spec.ROOT, [c for c in doc["configs"]
                        if c["name"] == w["config"]][0]["file"]))
