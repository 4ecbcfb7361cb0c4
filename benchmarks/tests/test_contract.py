"""BENCHMARK.json against the limits of the benchmark's contract that a
file can be checked for, so that an entry added later is refused here
and not by the driver."""

import json
import os
import re

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                    r"head_dim|expansion|experts_per_tok)")


def doc():
    path = os.path.join(spec.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    return spec.load_json(path)


def line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    d = doc()
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(d["paths"]) <= 16 and all(PATH.match(p) for p in d["paths"])
    assert len(d["command"]) <= 32 and all(line(w) for w in d["command"])
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    # a full check at the full 24 cells must fit into 43200 s
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    d = doc()
    assert 1 <= len(d["configs"]) <= 24
    used = {w["config"] for w in d["workloads"]}
    files = set()
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in d["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert isinstance(json.load(open(os.path.join(spec.ROOT, c["file"]))),
                          dict)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTHS.search(key), key
        cell = spec.load_json(os.path.join(
            os.path.dirname(os.path.join(spec.ROOT, c["file"])), "cell.json"))
        assert cell["source"] == c["source"]
        assert cell["reduced"] == c["reduced"]
    assert len({c["name"] for c in d["configs"]}) == len(d["configs"])


def test_workloads():
    d = doc()
    assert 1 <= len(d["workloads"]) <= 24
    pairs = set()
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in d["workloads"])
    assert four <= max(1, len(d["workloads"]) // 4)
    assert len({w["name"] for w in d["workloads"]}) == len(d["workloads"])


def test_metrics():
    d = doc()
    cells = {w["name"] for w in d["workloads"]}
    names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    assert len(names) == len(set(names))
    assert 1 <= len(d["end_to_end"]) <= 16 and 1 <= len(d["per_layer"]) <= 128
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in d["end_to_end"] + d["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    assert "setup_s" in names
    for name in cells:
        cell = spec.Cell(name)     # refuses a metric whose `moves` is missing
        assert "setup_s" in cell.names("end_to_end")
        assert len(cell.names("end_to_end")) >= 2
        assert len(cell.names("per_layer")) >= 1
