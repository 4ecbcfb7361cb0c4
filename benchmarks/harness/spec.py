"""What a cell is made of, found by name.

`BENCHMARK.json` names a workload's configuration and traffic mix; the
files behind the names live under `benchmarks/`:

    configs/<config>/config.json    the model's published config
    configs/<config>/cell.json      source, reduced, assumed, server args
    traffic/<traffic>.json          the mix: loop, clients, fixed multiset
    layer_metrics/<anything>.py     METRICS + read(run), one small reader

Which metrics a cell reports is read from `BENCHMARK.json` alone (a
metric with no `workloads` key belongs to every cell), so a new cell is
one entry there plus at most two new files, and no file here is edited.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The cell cannot be run as described; exit non-zero, no result."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class Cell:
    """One workload of BENCHMARK.json with its files resolved."""

    def __init__(self, workload: str, bench_dir: str = BENCH_DIR,
                 benchmark_json: str | None = None):
        self.bench_dir = bench_dir
        path = benchmark_json or os.path.join(
            os.path.dirname(bench_dir), "BENCHMARK.json")
        bench = load_json(path)
        entries = [w for w in bench["workloads"] if w["name"] == workload]
        if not entries:
            raise SpecError(
                f"no workload {workload!r} in {path}: have "
                + ", ".join(w["name"] for w in bench["workloads"]))
        self.entry = entries[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        self.config_dir = os.path.join(bench_dir, "configs",
                                       self.config_name)
        self.model_config = load_json(
            os.path.join(self.config_dir, "config.json"))
        self.cell = load_json(os.path.join(self.config_dir, "cell.json"))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, workload)]
        self.per_layer = [m for m in bench["per_layer"]
                          if _applies(m, workload)]
        judged = {m["name"] for m in self.end_to_end}
        for m in self.per_layer:
            if m["moves"] not in judged:
                raise SpecError(
                    f"cell {workload!r} lists the layer metric "
                    f"{m['name']!r}, which moves {m['moves']!r}, but "
                    "does not report that end-to-end metric")

    def names(self, which: str) -> list:
        return [m["name"] for m in getattr(self, which)]


def discover_layer_metrics(bench_dir: str = BENCH_DIR) -> dict:
    """{metric name: (declaration, read function)} from every .py file
    of layer_metrics/. A file declares METRICS (a list of {name, unit,
    layer, moves, source}) and read(run) -> {name: value or None}."""
    found = {}
    directory = os.path.join(bench_dir, "layer_metrics")
    for fname in sorted(os.listdir(directory)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + fname[:-3].replace(".", "_"),
            os.path.join(directory, fname))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        for decl in module.METRICS:
            if decl["name"] in found:
                raise SpecError(
                    f"layer metric {decl['name']!r} is declared twice "
                    f"(second time in {fname})")
            found[decl["name"]] = (decl, module.read)
    return found


def read_layer_metrics(cell: Cell, run: dict, found: dict | None = None,
                       log=None) -> dict:
    """The cell's per-layer metrics as {name: {"value", "unit"}}. A
    reader that finds nothing to read returns nothing for that name and
    the metric is left out of the line."""
    found = found if found is not None else discover_layer_metrics(
        cell.bench_dir)
    out, cache = {}, {}
    for m in cell.per_layer:
        name = m["name"]
        if name not in found:
            raise SpecError(
                f"BENCHMARK.json names the layer metric {name!r} but no "
                "file of layer_metrics/ declares it")
        decl, read = found[name]
        for key in ("unit", "layer", "moves", "source"):
            if decl[key] != m[key]:
                raise SpecError(
                    f"layer metric {name!r}: {key} is {decl[key]!r} in "
                    f"its file and {m[key]!r} in BENCHMARK.json")
        if read not in cache:
            try:
                cache[read] = read(run) or {}
            except Exception as e:  # noqa: BLE001 — one reader, one metric
                if log:
                    log(f"layer metric reader for {name!r} failed: "
                        f"{type(e).__name__}: {e}")
                cache[read] = {}
        value = cache[read].get(name)
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out
