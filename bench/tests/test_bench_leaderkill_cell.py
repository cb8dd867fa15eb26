"""The cell `s9-leaderkill-open` on the CPU, at a rate the CPU drives
through a whole fault period: correct through the kills, the range back
to acknowledging writes within tens of modeled ms (clients follow the
new leader instead of waiting out their 1-s attempt timeout), and the
readers of the recovery layer, on the run and on hand-built event logs."""

import dataclasses
import json
import time
from types import SimpleNamespace

import pytest

from bench import cells, failover, harness
from repro.core.cluster import Client, ClusterConfig

CPU_RATE = 5000.0
UNAVAILABLE = {"name": "unavailable_sim_s", "unit": "s"}
RECOVERY = ("election_sim_ms", "takeover_sim_ms", "failover_sim_ms",
            "catchup_sim_ms", "catchup_records_per_restart")


def cpu_cell(extra_e2e=()) -> cells.Cell:
    cell = cells.load_cell("s9-leaderkill-open")
    traffic = dict(cell.traffic, rate=CPU_RATE,
                   faults=dict(cell.traffic["faults"], min_periods=1))
    return dataclasses.replace(cell, traffic=traffic,
                               end_to_end=cell.end_to_end + tuple(extra_e2e))


def test_the_cell_is_the_section_9_deployment_under_leader_kills():
    cell = cells.load_cell("s9-leaderkill-open")
    assert cell.chips == 1
    assert cell.config["name"] == "spinnaker-s9-leaderkill"
    t = cell.traffic
    assert t["driver"] == "open" and t["read"] == "strong"
    assert 35000 <= t["rate"] <= 50000
    assert t["faults"] == {
        "period_s": 2.0, "rid_step": 1, "min_periods": 3,
        "schedule": ["at 0.25s crash leader of {rid}",
                     "at 1.25s restart crashed"]}
    assert {m["name"] for m in cell.per_layer} >= set(RECOVERY)
    assert {m["name"] for m in cell.end_to_end} == {
        "sim_ops_per_s", "op_p99_sim_ms", "setup_s"}


def test_the_leaderkill_deployment_keeps_every_number_of_section_9():
    """The deployment differs from `spinnaker-s9` in how it recovers, not
    in its cluster, data or guarantees."""
    s9 = json.loads((cells.BENCH / "configs" / "spinnaker-s9.json").read_text())
    cell = cells.load_cell("s9-leaderkill-open")
    shared = set(s9) - {"name", "source", "deployment", "assumed"}
    assert {k: cell.config[k] for k in shared} == {k: s9[k] for k in shared}
    assert cell.config["session_timeout_s"] == ClusterConfig().session_timeout
    assert cell.config["client_attempt_timeout_s"] == Client.ATTEMPT_TIMEOUT

@pytest.fixture(scope="module")
def untraced():
    return harness.run_cell(cpu_cell([UNAVAILABLE]), seed=2147483659,
                            seconds=6.0, trace=False,
                            t_setup0=time.perf_counter())


@pytest.fixture(scope="module")
def traced():
    return harness.run_cell(cpu_cell(), seed=3000000019, seconds=6.0,
                            trace=True, t_setup0=time.perf_counter())


def test_correct_and_available_through_the_kills(untraced):
    out = untraced
    assert out["correct"], out["_detail"]
    assert out["window"]["periods"] >= 1
    checks = out["checks"]
    for name in ("lost_acked_writes", "lin_violations", "unanswered"):
        assert checks[name][0] == 0, name
    # the parent's clients waited out the 1-s attempt timeout: ~1.0 s
    assert 0 < out["metrics"]["unavailable_sim_s"]["value"] < 0.25
    assert out["metrics"]["op_p99_sim_ms"]["value"] < 100.0


def test_recovery_readers_on_the_run(traced):
    assert traced["correct"], traced["_detail"]
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(RECOVERY) <= set(m)
    assert 0 < m["election_sim_ms"] < 50
    assert 0 < m["takeover_sim_ms"] < 50
    # the clients hear of the new leader no sooner than it is elected
    assert m["election_sim_ms"] <= m["failover_sim_ms"] < 50
    assert 0 < m["catchup_sim_ms"] < 500
    assert m["catchup_records_per_restart"] > 0


def _obs(events, kills):
    return SimpleNamespace(cluster_events=events, kills=kills)


def test_failover_reader_takes_the_first_client_per_killed_range():
    events = [
        {"t": 1.0, "kind": "node_crash", "node": 2},
        {"t": 1.003, "kind": "client_failover", "rid": 0, "client": "a"},
        {"t": 1.003, "kind": "client_failover", "rid": 0, "client": "b"},
        {"t": 1.005, "kind": "client_failover", "rid": 1, "client": "a"},
        {"t": 1.5, "kind": "client_failover", "rid": 0, "client": "a"},
        {"t": 3.004, "kind": "client_failover", "rid": 1, "client": "a"},
    ]
    kills = [(0, 1.0, 2, [0, 1]), (1, 3.0, 4, [1]), (2, 5.0, 0, [3])]
    # range 3 was never failed over: it is left out, not counted as 0
    assert failover.failover_ms(_obs(events, kills)) == \
        pytest.approx((3 + 5 + 4) / 3)
    assert failover.failover_ms(_obs(events, [])) is None
    assert failover.failover_ms(_obs([], kills)) is None


def test_catchup_readers_follow_the_restarted_node_only():
    events = [
        {"t": 1.0, "kind": "node_crash", "node": 2},
        # the surviving follower rejoins the new leader: not a restart
        {"t": 1.002, "kind": "follower_caught_up", "node": 3, "rid": 0,
         "records": 7},
        {"t": 2.0, "kind": "node_restart", "node": 2},
        {"t": 2.004, "kind": "follower_caught_up", "node": 2, "rid": 0,
         "records": 10},
        {"t": 2.006, "kind": "follower_caught_up", "node": 2, "rid": 1,
         "records": 20},
        # a later catch-up of the same replica is not its restart's
        {"t": 2.5, "kind": "follower_caught_up", "node": 2, "rid": 1,
         "records": 99},
        {"t": 3.0, "kind": "node_crash", "node": 2},
        {"t": 3.1, "kind": "follower_caught_up", "node": 2, "rid": 4,
         "records": 50},
        {"t": 4.0, "kind": "node_restart", "node": 2},
        {"t": 4.010, "kind": "follower_caught_up", "node": 2, "rid": 0,
         "records": 30},
    ]
    kills = [(0, 1.0, 2, [0]), (1, 3.0, 2, [1])]
    obs = _obs(events, kills)
    assert failover.catchup_ms(obs) == pytest.approx((4 + 6 + 10) / 3)
    assert failover.catchup_records_per_restart(obs) == \
        pytest.approx((10 + 20 + 30) / 2)
    # a kill whose node never came back within the log counts nothing
    never = _obs(events[:2], [(0, 1.0, 2, [0])])
    assert failover.catchup_ms(never) is None
    assert failover.catchup_records_per_restart(never) is None


def test_readers_find_nothing_in_a_log_without_the_events():
    """An event log without the failover and catch-up events, as a
    program without them writes: the readers return None and do not
    raise."""
    events = [{"t": 1.0, "kind": "node_crash", "node": 2},
              {"t": 1.001, "kind": "leader_takeover", "rid": 0},
              {"t": 2.0, "kind": "node_restart", "node": 2}]
    obs = _obs(events, [(0, 1.0, 2, [0])])
    for name in RECOVERY[2:]:
        assert cells.metric_reader(name)(obs) is None, name
