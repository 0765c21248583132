"""BENCHMARK.json against the rules its reader relies on."""
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[2]
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]


def metrics():
    return B["end_to_end"] + B["per_layer"]


def test_names_and_units_use_the_allowed_characters():
    names = [e["name"] for e in B["configs"] + B["workloads"] + metrics()]
    names += [w["config"] for w in B["workloads"]]
    names += [w["traffic"] for w in B["workloads"]]
    names += [k for c in B["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics())
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in B[group]}) == len(B[group])
    assert len({m["name"] for m in metrics()}) == len(metrics())
    for text in [e["why"] for e in B["configs"] + B["workloads"]] + \
            [m["layer"] for m in B["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in B["configs"]}
    for w in B["workloads"]:
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in B["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    assert {w["config"] for w in B["workloads"]} == set(configs)


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for m in B["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in B["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m.get("workloads", CELLS) for m in B["per_layer"])


def test_at_most_one_cell_in_two_asks_for_four_chips():
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert all(w["chips"] in (1, 4) for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)


def test_bounds_and_window_within_the_contract():
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= B["run_seconds"] <= 51
    assert B["command"][1] == "bench/run.py" and B["paths"] == ["bench"]
