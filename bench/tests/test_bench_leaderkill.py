"""The open-loop fault path on the CPU, at a low rate: leader kills every
period, the modeled unavailability, and the recovery readers.

The cell `s9-leaderkill-open` (the section 9 deployment, traffic
`bench/traffic/s9-leaderkill-open.json`) is not in `BENCHMARK.json` yet:
at its rate three fault periods do not fit in a run's window on the
chip's host (PERF.md, Open questions).  Its files and this path wait for
it; here it runs at a rate the CPU can drive through one period."""

import json
import time

import pytest

from bench import cells, harness

RECOVERY = [{"name": n, "unit": "ms", "moves": "unavailable_sim_s"}
            for n in ("election_sim_ms", "takeover_sim_ms")]
UNAVAILABLE = {"name": "unavailable_sim_s", "unit": "s"}


def leaderkill_cell(rate: float, min_periods: int) -> cells.Cell:
    bench = cells.BENCH
    config = json.loads((bench / "configs" / "spinnaker-s9.json").read_text())
    traffic = json.loads(
        (bench / "traffic" / "s9-leaderkill-open.json").read_text())
    traffic["rate"] = rate
    traffic["faults"]["min_periods"] = min_periods
    setup = {"name": "setup_s", "unit": "s"}
    return cells.Cell("s9-leaderkill-open", 1, config, traffic,
                      (UNAVAILABLE, setup), tuple(RECOVERY))


@pytest.fixture(scope="module")
def traced():
    return harness.run_cell(leaderkill_cell(1000.0, 1), seed=99,
                            seconds=8.0, trace=True,
                            t_setup0=time.perf_counter())


def test_correct_through_the_kills(traced):
    assert traced["correct"], traced["_detail"]
    assert traced["window"]["periods"] >= 1


def test_recovery_readers(traced):
    m = traced["metrics"]
    assert 0 < m["election_sim_ms"]["value"] < 2000
    assert 0 < m["takeover_sim_ms"]["value"] < 2000


def test_unavailable_is_read():
    out = harness.run_cell(leaderkill_cell(1000.0, 1), seed=98, seconds=6.0,
                           trace=False, t_setup0=time.perf_counter())
    assert out["correct"], out["_detail"]
    assert 0 < out["metrics"]["unavailable_sim_s"]["value"] < 2.0


def test_unavailable_counts_each_killed_range():
    class H:
        writes = [("a", 0, 1.2, True), ("a", 0, 1.5, True),
                  ("b", 0, 3.0, False), ("b", 0, 3.5, True)]

    class C:
        @staticmethod
        def range_of(key):
            return {"a": 0, "b": 1}[key]
    kills = [(0, 1.0, 2, [0, 1]), (1, 3.2, 4, [1])]
    # range 0: 0.2 s; range 1 after the first kill: 2.5 s (a failed write
    # does not count); range 1 after the second: 0.3 s
    assert harness.unavailable_s(kills, H, C, t_limit=9.0) == \
        pytest.approx((0.2 + 2.5 + 0.3) / 3)
    assert harness.unavailable_s([(0, 8.0, 1, [0])], H, C, 9.0) == \
        pytest.approx(1.0)
    assert harness.unavailable_s([], H, C, 9.0) is None
