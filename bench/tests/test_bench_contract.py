"""BENCHMARK.json keeps to the shape the benchmark's contract gives it:
keys, names, units, files under ``paths``, which cells report what, and a
run length whose full check fits its time."""
import json
import re

import pytest

from benchkit import ROOT

from bench import harness

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_names():
    assert set(BENCH) == KEYS["top"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        for e in BENCH[group]:
            extra = set(e) - KEYS[group]
            assert KEYS[group] <= set(e), (group, e["name"])
            assert extra <= ({"workloads"} if group in (
                "end_to_end", "per_layer") else set()), (group, extra)
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e and group != "end_to_end" and group != "per_layer":
                    assert _line(e[k]), (e["name"], k)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert _line(m["layer"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_files_and_command_stay_under_paths():
    paths = BENCH["paths"]
    assert 1 <= len(paths) <= 16 and len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths)
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_every_cell_reports_enough():
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert "setup_s" in bounds and bounds["setup_s"] <= 0.25
    assert all(0.01 <= b <= 0.25 for b in bounds.values())
    cells = {w["name"] for w in BENCH["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell = harness.find_cell(BENCH, w["name"], ROOT)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            harness.metric_reader(ROOT / "bench", m["name"])
        for m in cell.end_to_end:
            harness.metric_reader(ROOT / "bench", m["name"])
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(m.get("workloads", ())) <= cells
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(cells) // 2)


@pytest.mark.parametrize("cells", [24])
def test_run_length_fits_a_full_check(cells):
    r = BENCH["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    runs = 2 + 14 * cells
    assert runs * (r + 60) + cells * 2 * 90 + 1200 <= 43200
