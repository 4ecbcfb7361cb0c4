"""`setup_programs_s` and `setup_weights_s` (layer_metrics/startup.py):
read from the scrape taken as the window opens, in the shape
`harness/server.parse_metrics` gives it, found by name, and reported in
every cell."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec
from harness.server import parse_metrics

NAMES = ("setup_programs_s", "setup_weights_s")

# /metrics of a served toy cell as its window opened (a rehearsal on the
# CPU, PR 67), cut to the families the reader reads and two it does not
SCRAPE = """\
# HELP cake_startup_phase_seconds Seconds of each named start-up phase
# TYPE cake_startup_phase_seconds gauge
cake_startup_phase_seconds{phase="boot"} 0.109112
cake_startup_phase_seconds{phase="import_jax"} 1.694012
cake_startup_phase_seconds{phase="weights"} 0.395758
cake_startup_phase_seconds{phase="quantize"} 0.25
cake_startup_phase_seconds{phase="engine"} 0.082048
cake_startup_phase_seconds{phase="unnamed"} 0.24779
# TYPE cake_startup_healthy_seconds gauge
cake_startup_healthy_seconds 5.03962
# TYPE cake_jit_trace_seconds_total counter
cake_jit_trace_seconds_total 0.69798
# TYPE cake_jit_lower_seconds_total counter
cake_jit_lower_seconds_total 1.175879
# TYPE cake_jit_backend_seconds_total counter
cake_jit_backend_seconds_total 0.320799
# TYPE cake_jit_cache_load_seconds_total counter
cake_jit_cache_load_seconds_total 0.298624
# TYPE cake_jit_cost_analysis_seconds_total counter
cake_jit_cost_analysis_seconds_total 0.5
# TYPE cake_jit_cache_hits_total counter
cake_jit_cache_hits_total 27
cake_jit_compiles_total{fn="mixed_step"} 2
"""

# the parent commit's: the accountant's counter and no more
PARENT_SCRAPE = """\
# TYPE cake_jit_compiles_total counter
cake_jit_compiles_total{fn="mixed_step"} 2
cake_jit_compile_seconds_count 4
cake_jit_compile_seconds_sum 9.5
"""


def test_both_names_from_a_recorded_scrape():
    found = spec.discover_layer_metrics()
    for name in NAMES:
        decl, _ = found[name]
        assert decl == {"name": name, "unit": "s",
                        "layer": "entry and loader", "moves": "setup_s",
                        "source": "program_span"}
    read = found[NAMES[0]][1]
    assert read is found[NAMES[1]][1]
    got = read({"metrics_0": parse_metrics(SCRAPE)})
    # the four that add up; the cache's load lies inside the backend's
    assert got["setup_programs_s"] == pytest.approx(
        0.69798 + 1.175879 + 0.320799 + 0.5)
    # weights + quantize; no weights_ready was filed
    assert got["setup_weights_s"] == pytest.approx(0.395758 + 0.25)
    assert set(got) == set(NAMES)


def test_each_name_needs_only_its_own_families():
    read = spec.discover_layer_metrics()[NAMES[0]][1]
    only_programs = {k: v for k, v in parse_metrics(SCRAPE).items()
                     if k.startswith("cake_jit_")}
    assert set(read({"metrics_0": only_programs})) == {"setup_programs_s"}
    only_phases = {k: v for k, v in parse_metrics(SCRAPE).items()
                   if k.startswith("cake_startup_")}
    assert set(read({"metrics_0": only_phases})) == {"setup_weights_s"}
    # phases but no weights among them (the clock has not got there)
    early = {'cake_startup_phase_seconds{phase="boot"}': 0.1}
    assert read({"metrics_0": early}) == {}


def test_a_program_without_the_families_reports_nothing_and_raises_nothing():
    read = spec.discover_layer_metrics()[NAMES[0]][1]
    assert read({"metrics_0": parse_metrics(PARENT_SCRAPE)}) == {}
    assert read({"metrics_0": {}}) == {} and read({}) == {}
    assert read({"metrics_0": None}) == {}


def test_the_names_are_listed_once_and_for_every_cell():
    # (how many names and bytes the file may hold: test_contract.py)
    doc = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    listed = [m for m in doc["per_layer"] if m["name"] in NAMES]
    assert sorted(m["name"] for m in listed) == sorted(NAMES)
    for entry in listed:
        assert entry["better"] == "lower" and "workloads" not in entry
    for w in doc["workloads"]:
        cell = spec.Cell(w["name"])
        assert set(NAMES) <= set(cell.names("per_layer")), w["name"]
        assert "setup_s" in cell.names("end_to_end")


def test_a_rehearsed_cell_prints_both_names_on_its_layers_line():
    """The whole path on the CPU: the program files its phases and
    counts its programs, the harness scrapes them as the window opens,
    the reader finds them. ~1 min."""
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "mistral7b.chat-closed", "--seed", "7",
         "--seconds", "20", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    layers = [ln for ln in proc.stderr.splitlines()
              if ln.startswith("layers: ")]
    assert len(layers) == 1
    said = json.loads(layers[0][len("layers: "):])
    for name in NAMES + ("healthy_s", "warmup_s"):
        assert said[name] > 0, name
    # what the program says it spent cannot pass the harness's own clock
    line = json.loads(proc.stdout.splitlines()[-1])
    assert said["setup_weights_s"] < said["healthy_s"]
    assert said["setup_programs_s"] < line["metrics"]["setup_s"]["value"]
