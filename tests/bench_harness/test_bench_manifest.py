"""``BENCHMARK.json`` against the benchmark's contract, and the files it names."""
import json
import re
from pathlib import Path

import pytest

import peaks

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def _cells(metric):
    return metric.get("workloads", [c["name"] for c in MAN["workloads"]])


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(MAN["paths"]) <= 16 and all(PATH.match(p) and ".." not in p for p in MAN["paths"])
    assert 1 <= len(MAN["command"]) <= 32 and all(_line(w) for w in MAN["command"])
    assert not any(w.startswith("/") or ".." in w for w in MAN["command"])
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    # a full check of 24 cells fits its 12 hours
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_one_line_texts():
    names = [c["name"] for c in MAN["configs"]] + [w["name"] for w in MAN["workloads"]]
    names += [m["name"] for m in METRICS] + [w["traffic"] for w in MAN["workloads"]]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for group in (MAN["configs"], MAN["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for text in [c["why"] for c in MAN["configs"]] + [c["source"] for c in MAN["configs"]] + \
            [w["why"] for w in MAN["workloads"]] + [m["layer"] for m in MAN["per_layer"]]:
        assert _line(text), text


def test_entries_have_just_their_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert [m for m in MAN["end_to_end"] if m["name"] == "setup_s"][0]["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in _cells(m):
            assert cell in cells
            assert cell in _cells(e2e[m["moves"]]), (m["name"], cell)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    pairs = set()
    for w in MAN["workloads"]:
        e2e = [m["name"] for m in MAN["end_to_end"] if w["name"] in _cells(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in _cells(m) for m in MAN["per_layer"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


def test_named_files_exist_under_the_paths():
    def under(path):
        return any(path.startswith(p.rstrip("/") + "/") for p in MAN["paths"])

    files = [c["file"] for c in MAN["configs"]]
    assert len(set(files)) == len(files)
    for c in MAN["configs"]:
        assert under(c["file"]) and (ROOT / c["file"]).is_file()
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and set(body["reduced"]) == set(c["reduced"])
        assert all(k in body for k in c["reduced"])
    for w in MAN["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in METRICS:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    assert under(MAN["command"][1]) and (ROOT / MAN["command"][1]).is_file()


def test_peaks_know_the_v5e_and_refuse_an_unknown_chip():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and "source" in v5e
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")
