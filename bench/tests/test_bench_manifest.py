"""`BENCHMARK.json` and the files the harness finds by name in it."""

import json
import re

import pytest

from bench import cells, peaks

M = cells.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def _text_ok(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    assert M["paths"] == ["bench"]
    assert 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) < 64 * 1024


def test_names_and_units_use_allowed_characters():
    names = [x["name"] for x in M["configs"] + M["workloads"] + METRICS]
    names += [w["config"] for w in M["workloads"]]
    names += [w["traffic"] for w in M["workloads"]]
    names += [k for c in M["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in (M["configs"], M["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    texts = [x["why"] for x in M["configs"] + M["workloads"]]
    texts += [c["source"] for c in M["configs"]]
    texts += [m["layer"] for m in M["per_layer"]] + M["command"]
    assert all(_text_ok(t) for t in texts)


def test_entries_have_just_their_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES
    assert all(m["better"] in {"lower", "higher"} for m in METRICS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = cells.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    assert {m["moves"] for m in cell.per_layer} <= e2e
    assert set(cell.traffic) >= {"driver", "read", "mix", "warmup_sim_s"}
    assert sum(cell.traffic["mix"].values()) == pytest.approx(1.0)
    assert cell.config["name"] == next(
        w["config"] for w in M["workloads"] if w["name"] == name)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no cell"):
        cells.load_cell("no-such-cell")


def test_configs_are_used_and_lie_under_paths():
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    files = [c["file"] for c in M["configs"]]
    assert len(set(files)) == len(files)
    for c in M["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert set(c["reduced"]) <= set(
            json.loads((cells.ROOT / c["file"]).read_text()))


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_every_per_layer_metric_has_its_reader(metric):
    assert callable(cells.metric_reader(metric))


def test_per_layer_workloads_name_cells():
    for m in METRICS:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_setup_bound():
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25 and setup["better"] == "lower"


def test_roofline_byte_count():
    # four int32/float32 outputs per op, whatever computes the sample
    assert peaks.sampler_output_bytes(8192) == 131072
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v99", "hbm_bytes_per_s")


def test_roofline_reader():
    read = cells.metric_reader("sampler_roofline")

    class Trace:
        sampler_s, sampler_runs = 0.004, 4

    class Obs:
        trace, device_kind, sampler_batch = Trace, "TPU v5 lite", 8192
    # 131072 B / 819 GB/s = 0.16 us of a 1 ms run
    assert read(Obs) == pytest.approx(131072 / 819e9 / 1e-3 * 100)
    Trace.sampler_runs = 0
    assert read(Obs) is None
