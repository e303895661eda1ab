"""BENCHMARK.json against the benchmark's contract: allowed characters,
exact keys, files that exist, a reader for every metric, and metrics
that every cell naming them can report."""

import re

import pytest

import _paths  # noqa: F401
from chipbench import spec

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(BENCH) == TOP
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (spec.ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(_text(c) for c in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
            assert (spec.ROOT / word).is_file()
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fits_a_full_check():
    s = BENCH["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    # a full check of 24 cells: runs, compiles and spare time
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and _text(entry["source"])
    assert _text(entry["why"]) and len(entry["reduced"]) <= 16
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    conf = spec.load_json(spec.ROOT / entry["file"])
    assert conf["name"] == entry["name"]
    assert conf["reduced"] == entry["reduced"]
    assert conf["source"] == entry["source"]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and _text(cell["why"])
    assert (spec.HERE / "traffic" / f"{cell['traffic']}.json").is_file()
    c = spec.cell(cell["name"])
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names


def test_cells_unique_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(set(CELLS))
    assert 1 <= len(CELLS) <= 24
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(metric):
    e2e = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"}
    keys |= {"bound"} if e2e else {"layer", "moves"}
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    # its own reader, or the one its variants share (spec.reader)
    assert any((spec.HERE / "metrics" / f"{n}.py").is_file()
               for n in (metric["name"], metric["name"].split(".")[0]))
    assert callable(spec.reader(metric["name"]))
    for w in metric.get("workloads", []):
        assert w in CELLS
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert _text(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_unique_metric_names_and_setup_everywhere():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"][0]
    assert "workloads" not in setup and setup["bound"] <= 0.25


def test_files_under_paths_are_named_from_name_characters():
    part = re.compile(r"^[A-Za-z0-9_.\-]+$")
    for path in spec.HERE.rglob("*"):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(spec.ROOT)
        assert all(part.match(p) for p in rel.parts), rel
