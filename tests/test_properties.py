"""Property-based protocol tests (hypothesis): random fault schedules must
never violate Spinnaker's guarantees (§8.1):

  P1  durability: an acknowledged write is never lost under any
      crash-restart schedule (disks survive; only volatile state is lost);
  P2  version linearity: committed versions per key are unique and the
      final state corresponds to an actually-issued write;
  P3  leader uniqueness: at most one open leader per cohort, epochs
      strictly monotone;
  P4  timeline monotonicity: a replica's applied version for a key never
      decreases;
  P5  convergence: after healing, all replicas agree on committed state.
"""

import pytest

pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, example, given, settings, strategies as st

from repro.core import (ClusterConfig, ErrorCode, NodeConfig, ReplicaConfig,
                        Simulator, SpinnakerCluster, key_of)
from repro.core.replica import Role

KEYS = [key_of(1), key_of(2), key_of(3)]   # all land in a small cluster's ranges

action = st.one_of(
    st.tuples(st.just("put"), st.integers(0, len(KEYS) - 1)),
    st.tuples(st.just("crash"), st.integers(0, 2)),
    st.tuples(st.just("crash_noexpire"), st.integers(0, 2)),
    st.tuples(st.just("restart"), st.integers(0, 2)),
    st.tuples(st.just("tick"), st.sampled_from([0.1, 0.5, 1.5, 3.0])),
)


def drive(sim, pred, budget, slice_=0.05):
    """Run sim until pred() or sim-time budget exhausted."""
    deadline = sim.now + budget
    while sim.now < deadline and not pred():
        sim.run(until=min(deadline, sim.now + slice_))
    return pred()


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(seed=st.integers(0, 2**16), schedule=st.lists(action, min_size=1,
                                                     max_size=30))
# a restart once re-applied only up to the commit marker its log had made
# durable, below what the replica had applied before the crash (P4)
@example(seed=0, schedule=[("put", 0)] * 11 + [("crash", 0), ("restart", 0)])
def test_no_acked_write_lost_under_crash_restart(seed, schedule):
    sim = Simulator(seed=seed)
    cfg = ClusterConfig(
        n_nodes=3,
        node=NodeConfig(replica=ReplicaConfig(commit_period=0.25)),
        session_timeout=1.0)
    cluster = SpinnakerCluster(sim, cfg)
    cluster.start()
    drive(sim, lambda: all(cluster.leader_replica(r) is not None
                           for r in range(3)), 30.0)

    client = cluster.make_client()
    up = {0: True, 1: True, 2: True}
    acked: dict[str, tuple[bytes, int]] = {}
    issued: dict[str, set[bytes]] = {k: set() for k in KEYS}
    max_seen_version: dict[tuple[int, str], int] = {}
    wseq = 0

    def check_leader_uniqueness():
        for rid in range(3):
            leaders = [m for m in cluster.cohort(rid)
                       if cluster.nodes[m].replicas[rid].role is Role.LEADER
                       and cluster.nodes[m].has_session()]
            assert len(leaders) <= 1, f"two live leaders for range {rid}"

    def check_timeline_monotonic():
        # P4: per-replica applied versions never decrease
        for nid, node in cluster.nodes.items():
            for rid, rep in node.replicas.items():
                for key in KEYS:
                    cell = rep.store.get(key, "c")
                    if cell is None:
                        continue
                    prev = max_seen_version.get((nid, key), 0)
                    if node.up:
                        assert cell.version >= prev, \
                            f"replica n{nid} went back in time on {key}"
                    max_seen_version[(nid, key)] = max(prev, cell.version)

    for act in schedule:
        kind = act[0]
        if kind == "put":
            key = KEYS[act[1]]
            wseq += 1
            val = f"{key}-w{wseq}".encode()
            issued[key].add(val)
            box = []
            # bind THIS box (late replies from earlier, still-retrying puts
            # must not land in a rebound list)
            client.put(key, "c", val, lambda r, b=box: b.append(r))
            done = drive(sim, lambda b=box: bool(b), 8.0)
            if done and box[0].ok:
                acked[key] = (val, box[0].version)
        elif kind in ("crash", "crash_noexpire") and up[act[1]]:
            cluster.crash_node(act[1],
                               expire_session=(kind == "crash"))
            up[act[1]] = False
        elif kind == "restart" and not up[act[1]]:
            cluster.restart_node(act[1])
            up[act[1]] = True
        elif kind == "tick":
            sim.run_for(act[1])
        check_leader_uniqueness()
        check_timeline_monotonic()

    # heal everything and let the system settle
    for nid, alive in up.items():
        if not alive:
            cluster.restart_node(nid)
    ok = drive(sim, lambda: all(cluster.leader_replica(r) is not None
                                for r in range(3)), 60.0)
    assert ok, "cluster failed to re-elect leaders after full heal"
    sim.run_for(3.0)   # commit messages propagate

    # P1/P2: strong reads see every acknowledged write (or something newer
    # that was actually issued)
    for key, (val, version) in acked.items():
        box = []
        client.get(key, "c", True, lambda r, b=box: b.append(r))
        assert drive(sim, lambda b=box: bool(b), 30.0), "strong read stalled"
        res = box[0]
        assert res.ok, f"committed key {key} unreadable: {res.code}"
        assert res.version >= version, \
            f"lost write {val!r} v{version}; got v{res.version}"
        if res.version == version:
            assert res.value == val
        else:
            assert res.value in issued[key], "fabricated value"

    # P5: replicas converge on committed state
    sim.run_for(2.0)
    for rid in range(3):
        lead = cluster.leader_replica(rid)
        assert lead is not None
        for key in KEYS:
            if cluster.range_of(key) != rid:
                continue
            lcell = lead.store.get(key, "c")
            for m in cluster.cohort(rid):
                rep = cluster.nodes[m].replicas[rid]
                if rep.role is Role.FOLLOWER:
                    fcell = rep.store.get(key, "c")
                    if lcell is None:
                        continue
                    assert fcell is not None and fcell.version == lcell.version, \
                        f"follower n{m} diverged on {key}"


@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(0, 2**16),
       n_writers=st.integers(2, 4),
       n_rounds=st.integers(2, 8))
def test_conditional_put_is_linear_under_contention(seed, n_writers, n_rounds):
    """Optimistic concurrency (§3): concurrent conditional increments — the
    counter must equal exactly the number of successful cond-puts."""
    sim = Simulator(seed=seed)
    cluster = SpinnakerCluster(sim, ClusterConfig(n_nodes=3))
    cluster.start()
    drive(sim, lambda: all(cluster.leader_replica(r) is not None
                           for r in range(3)), 30.0)
    clients = [cluster.make_client(f"c{i}") for i in range(n_writers)]
    key = KEYS[0]
    clients[0].sync_put(key, "n", 0)

    successes = [0]

    def attempt(client, rounds_left):
        if rounds_left == 0:
            return

        def on_get(res):
            if not res.ok:
                return

            def on_cas(r2):
                if r2.ok:
                    successes[0] += 1
                attempt(client, rounds_left - 1)

            client.conditional_put(key, "n", res.value + 1, res.version,
                                   on_cas)

        client.get(key, "n", True, on_get)

    for cl in clients:
        attempt(cl, n_rounds)
    sim.run_for(60.0)

    final = clients[0].sync_get(key, "n", consistent=True)
    assert final.ok
    assert final.value == successes[0], \
        f"counter {final.value} != successful cond-puts {successes[0]}"
    assert final.version == successes[0] + 1  # initial put + each success
