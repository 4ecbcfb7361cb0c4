"""`BENCHMARK.json`'s `per_layer` list against the reader files: every
name has one reader and every reader's names are listed, the caps, and
the rule that a metric's name does not end in a cell's name, so that
the next cell JOINS a metric by list and does not copy it (PR 55: 20
of 128 entries were three readings under seven cells' names)."""

import importlib.util
import os
import re

from harness import spec
from test_contract import CELLS_CAP, END_TO_END_CAP, PER_LAYER_CAP

# suffixes that say what a reading is, not which cell it is in
ALLOWED_SUFFIXES = {
    "tok",    # the cell is judged by tokens: the metric moves out_tok_s
    "obs",    # observed, demoted from a judged metric (tpot_p50_ms.obs)
}
# readings BY CLASS of one traffic file, and that file's own two-level
# TPOT: they exist in no other mix
BY_CLASS = {"ttft_p50_ms.longshort-s1k", "ttft_p50_ms.longshort-d8k",
            "tpot_p50_ms.longshort"}
# gone at PR 55, and not to come back under these names
GONE = {"step_gap_p50_ms", "host_schedule_p50_ms", "host_sample_p50_ms",
        "loop_covered_pct", "emit_unnamed_us_per_token", "ttft_p50_ms.dense"}
COUNT_AT_PR_55 = 108


def doc():
    return spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))


def declared_by_file() -> dict:
    """{reader file: [names its METRICS declare]}."""
    out = {}
    directory = os.path.join(spec.BENCH_DIR, "layer_metrics")
    for fname in sorted(os.listdir(directory)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        s = importlib.util.spec_from_file_location(
            "names_of_" + fname[:-3], os.path.join(directory, fname))
        module = importlib.util.module_from_spec(s)
        s.loader.exec_module(module)
        out[fname] = [m["name"] for m in module.METRICS]
    return out


def test_every_name_has_one_reader_and_every_reader_is_listed():
    listed = [m["name"] for m in doc()["per_layer"]]
    assert len(listed) == len(set(listed))
    files = declared_by_file()
    owners = {}
    for fname, names in files.items():
        assert len(names) == len(set(names)), fname
        for name in names:
            owners.setdefault(name, []).append(fname)
    twice = {n: f for n, f in owners.items() if len(f) > 1}
    assert not twice, twice
    # a name with no reader is refused at run time, in a traced run on
    # the chip (`spec.read_layer_metrics`); a declared name that no cell
    # lists is dead code that a reviewer takes for a reading
    assert set(listed) - set(owners) == set()
    assert set(owners) - set(listed) == set()
    assert not GONE & (set(listed) | set(owners))


def test_the_caps_and_the_count_that_stands():
    d = doc()
    assert (PER_LAYER_CAP, END_TO_END_CAP, CELLS_CAP) == (128, 16, 24)
    assert len(d["per_layer"]) <= PER_LAYER_CAP
    assert len(d["end_to_end"]) <= END_TO_END_CAP
    assert len(d["workloads"]) <= CELLS_CAP
    # PR 55 brought the list from 128 to 108; what a later cell adds is
    # names for what no cell has (README, "Adding things"), a handful a
    # configuration: the room has to last
    assert len(d["per_layer"]) >= COUNT_AT_PR_55 - 20
    cells_since = len(d["workloads"]) - 11
    assert len(d["per_layer"]) <= COUNT_AT_PR_55 + 2 + 6 * max(0, cells_since)


def cell_words(d: dict) -> set:
    """What names a cell: a workload's two halves, its configuration
    and its traffic file, whole and by their first word."""
    words = set()
    for w in d["workloads"]:
        for text in (w["name"], *w["name"].split(".", 1), w["config"],
                     w["traffic"]):
            words.add(text)
            words.add(re.split(r"[-.]", text)[0])
    return words


def test_no_name_ends_in_a_cells_or_a_traffic_files_name():
    d = doc()
    words = cell_words(d)
    assert {"longdoc", "agent", "reason", "longshort", "code", "longreply",
            "kexaone", "glm52", "chat", "longdoc-closed"} <= words
    for m in d["per_layer"] + d["end_to_end"]:
        name = m["name"]
        if "." not in name or name in BY_CLASS:
            continue
        suffix = name.split(".", 1)[1]
        assert suffix not in GONE
        if suffix in ALLOWED_SUFFIXES:
            continue
        assert suffix not in words and re.split(r"[-.]", suffix)[0] \
            not in words, (
            f"{name}: a metric named after a cell is a copy the next "
            "cell makes again; give it one name and a `workloads` list")
    # the exceptions are what they say: each names a class of its mix
    listed = {m["name"] for m in d["per_layer"]}
    assert BY_CLASS <= listed
    classes = {c["name"] for c in spec.Cell(
        "dots3.longshort-closed").traffic["prompt_classes"]}
    assert {"s1k", "d8k"} <= classes


def test_a_cell_lists_no_metric_whose_moves_it_does_not_report():
    d = doc()
    for w in d["workloads"]:
        cell = spec.Cell(w["name"])          # raises SpecError if it does
        judged = set(cell.names("end_to_end"))
        assert {m["moves"] for m in cell.per_layer} <= judged
        # every cell reads the stream writer's two, and its mixed step
        # under the name that moves what the cell is judged by
        layers = set(cell.names("per_layer"))
        assert {"stream_writer_share_pct", "stream_chunks_per_wake"} <= layers
        for plain in ("mixed_step_ms", "mixed_step_device_ms"):
            assert (plain in layers) <= ("ttft_mean_ms" in judged)
            assert (plain + ".tok" in layers) <= (
                "ttft_mean_ms" not in judged)
