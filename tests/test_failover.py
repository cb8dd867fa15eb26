"""Leader failover as the client sees it, and the recovery path's events.

- a client follows the coordination service to a range's new leader and
  sends again what was waiting at the dead one (a put, a strong read, a
  multi_get's fan-out), long before its 1-s attempt timeout;
- with no leader change the client's attempts and retries are untouched;
- `local_recovery`, `follower_caught_up` and `client_failover` are logged
  once per replica or per leader change, with their counts;
- the host profile's `recovery` layer keeps the layers adding up to the
  wall in integer ns.
"""

import pytest

from repro.core import (ClusterConfig, NodeConfig, ReplicaConfig, Simulator,
                        SpinnakerCluster, key_of)
from repro.core.cluster import Client
from repro.obs.hostprof import HostProfile


def make_cluster(n=3, seed=0):
    sim = Simulator(seed=seed)
    cfg = ClusterConfig(
        n_nodes=n, node=NodeConfig(replica=ReplicaConfig(commit_period=0.25)))
    cluster = SpinnakerCluster(sim, cfg)
    cluster.start()
    cluster.settle()
    return sim, cluster


def events(cluster, kind, t0=0.0):
    return [e for e in cluster.obs.events.events
            if e["kind"] == kind and e["t"] >= t0]


def run_until(sim, pred, budget=5.0, step=1e-4):
    deadline = sim.now + budget
    while not pred() and sim.now < deadline:
        sim.run(until=sim.now + step)
    return pred()


def open_after(cluster, rid, t0):
    return next(e["t"] for e in events(cluster, "leader_open", t0)
                if e["rid"] == rid)


def test_put_in_flight_at_a_crashed_leader_is_acked_by_the_new_one():
    sim, cluster = make_cluster()
    c = cluster.make_client()
    key = key_of(10)
    rid = cluster.range_of(key)
    assert c.sync_put(key, "c", b"v0").ok          # routes, arms the watch
    old = cluster.leader_replica(rid)
    got = []
    c.put(key, "c", b"v1", got.append)
    # the put reached the leader and waits there for its quorum
    assert run_until(sim, lambda: old.pending_reply)
    t_crash = sim.now
    cluster.crash_node(old.node.node_id)
    assert run_until(sim, lambda: got, budget=2.0)
    t_ack = sim.now
    assert got[0].ok
    new = cluster.leader_replica(rid)
    assert new.node.node_id != old.node.node_id
    assert t_ack - open_after(cluster, rid, t_crash) < 0.050
    assert t_ack - t_crash < Client.ATTEMPT_TIMEOUT / 4
    assert c.attempt_timeouts == 0
    assert c.failovers == 1 and c.failover_resends == 1
    assert c.sync_get(key, "c").value == b"v1"


def test_strong_read_routed_to_the_dead_leader_is_answered_by_the_new():
    sim, cluster = make_cluster()
    c = cluster.make_client()
    key = key_of(40_000)
    rid = cluster.range_of(key)
    assert c.sync_put(key, "c", b"x").ok
    old = cluster.leader_replica(rid).node.node_id
    got = []
    c.get(key, "c", True, got.append)              # sent to `old`
    cluster.crash_node(old)
    assert run_until(sim, lambda: got, budget=2.0)
    assert got[0].ok and got[0].value == b"x"
    assert got[0].latency < 0.1
    assert cluster.leader_replica(rid).node.node_id != old
    assert c.attempt_timeouts == 0 and c.failover_resends == 1


def test_multi_get_spanning_the_killed_range_completes():
    sim, cluster = make_cluster()
    c = cluster.make_client()
    keys = [key_of(i) for i in (10, 40_000, 80_000)]
    rids = {cluster.range_of(k) for k in keys}
    assert len(rids) == 3
    for k in keys:
        assert c.sync_put(k, "c", k.encode()).ok
    victim = cluster.leader_replica(cluster.range_of(keys[1])).node.node_id
    got = []
    c.multi_get([(k, "c") for k in keys], True, got.append)
    cluster.crash_node(victim)
    assert run_until(sim, lambda: got, budget=2.0)
    assert [r.value for r in got[0]] == [k.encode() for k in keys]
    assert max(r.latency for r in got[0]) < 0.1
    assert c.attempt_timeouts == 0 and c.failover_resends >= 1


def test_no_leader_change_leaves_attempts_and_retries_alone():
    sim, cluster = make_cluster(n=5, seed=3)
    c = cluster.make_client()
    done = []
    for i in range(300):
        k = key_of(i * 331)
        c.put(k, "c", b"v", done.append)
        c.get(k, "c", True, done.append)
    c.multi_get([(key_of(i * 997), "c") for i in range(60)], True,
                done.append)
    assert run_until(sim, lambda: len(done) == 601, budget=10.0, step=0.01)
    assert c.retries == 0 and c.attempt_timeouts == 0
    assert c.failovers == 0 and c.failover_resends == 0
    assert events(cluster, "client_failover") == []
    # every attempt left the waiting lists when it was answered
    assert not any(c._waiting.values())


def test_recovery_events_once_per_replica_and_leader_change():
    sim, cluster = make_cluster(n=3, seed=1)
    clients = [cluster.make_client("a"), cluster.make_client("b")]
    key = key_of(10)
    rid = cluster.range_of(key)
    for c in clients:
        assert c.sync_put(key, "c", b"w").ok
    for i in range(7):
        assert clients[0].sync_put(key_of(10 + i), "c", b"v").ok
    victim = cluster.leader_replica(rid).node.node_id
    t_crash = sim.now
    cluster.crash_node(victim)
    sim.run_for(1.0)
    new = cluster.leader_replica(rid).node.node_id
    failovers = [e for e in events(cluster, "client_failover", t_crash)
                 if e["rid"] == rid]
    # one per client per leader change, none per op
    assert sorted(e["client"] for e in failovers) == ["a", "b"]
    assert all((e["old"], e["new"], e["resent"]) == (victim, new, 0)
               for e in failovers)

    # writes the restarted replica missed, then its restart
    for i in range(5):
        assert clients[0].sync_put(key_of(20 + i), "c", b"u").ok
    t_restart = sim.now
    cluster.restart_node(victim)
    sim.run_for(1.0)
    node = cluster.nodes[victim]
    recovered = events(cluster, "local_recovery", t_restart)
    assert sorted(e["rid"] for e in recovered) == sorted(node.replicas)
    assert all(e["node"] == victim for e in recovered)
    # the 8 writes the range's replicas had applied, re-applied from the log
    applied = {e["rid"]: e["records"] for e in recovered}
    assert applied[rid] == 9
    caught_up = [e for e in events(cluster, "follower_caught_up", t_restart)
                 if e["node"] == victim]
    assert sorted(e["rid"] for e in caught_up) == sorted(node.replicas)
    by_rid = {e["rid"]: e for e in caught_up}
    # the 5 writes it missed, and nothing it already had
    assert by_rid[rid]["records"] == 5
    assert by_rid[rid]["leader"] == new
    assert node.replicas[rid].store.get(key_of(24), "c").value == b"u"


@pytest.mark.parametrize("disk_survives", [True, False])
def test_recovery_layer_telescopes_to_the_wall(disk_survives):
    sim, cluster = make_cluster(n=3, seed=2)
    c = cluster.make_client()
    for i in range(20):
        assert c.sync_put(key_of(i * 1000), "c", b"v").ok
    hp = HostProfile().start(sim, cluster.net)
    victim = cluster.leader_replica(0).node.node_id
    cluster.crash_node(victim, lose_disk=not disk_survives)
    sim.run_for(0.5)
    for i in range(20):
        assert c.sync_put(key_of(i * 1000), "c", b"w").ok
    cluster.restart_node(victim)
    sim.run_for(0.5)
    hp.stop()
    s = hp.summary()
    assert s["self_ns"]["recovery"] > 0
    assert sum(s["self_ns"].values()) == s["wall_ns"]
