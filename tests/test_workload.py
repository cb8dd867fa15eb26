"""Workload engine tests: generators, drivers, fault-schedule DSL,
partitions, batched reads, and timeline-read monotonicity across a leader
failover (§8.1, Figs. 9-10)."""

import collections
import math

import numpy as np
import pytest

from repro.core import ClusterConfig, Simulator, SpinnakerCluster, key_of
from repro.core.sim import Network
from repro.workload import (ClosedLoopDriver, ExperimentConfig, OpKind,
                            OpLog, OpStream, OpenLoopDriver,
                            SpinnakerAdapter, WorkloadSpec, parse_schedule,
                            run_spinnaker_workload)
from repro.workload.generators import _coprime_multiplier
from repro.workload.metrics import LatencyHistogram


def make_cluster(n=5, seed=0, **kw):
    sim = Simulator(seed=seed)
    cluster = SpinnakerCluster(sim, ClusterConfig(n_nodes=n, **kw))
    cluster.start()
    cluster.settle()
    return sim, cluster


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------


def test_stream_deterministic_from_seed():
    spec = WorkloadSpec(num_keys=500)
    a = [OpStream(spec, seed=7).next_op() for _ in range(1)]
    s1, s2 = OpStream(spec, seed=7), OpStream(spec, seed=7)
    ops1 = [s1.next_op() for _ in range(5000)]
    ops2 = [s2.next_op() for _ in range(5000)]
    assert ops1 == ops2
    s3 = OpStream(spec, seed=8)
    assert [s3.next_op() for _ in range(5000)] != ops1


def test_op_mix_proportions():
    spec = WorkloadSpec(num_keys=100, read_frac=0.5, write_frac=0.3,
                        rmw_frac=0.1, cond_frac=0.1)
    s = OpStream(spec, seed=0)
    kinds = collections.Counter(s.next_op().kind for _ in range(20000))
    assert kinds[OpKind.READ] / 20000 == pytest.approx(0.5, abs=0.02)
    assert kinds[OpKind.WRITE] / 20000 == pytest.approx(0.3, abs=0.02)
    assert kinds[OpKind.RMW] / 20000 == pytest.approx(0.1, abs=0.01)
    assert kinds[OpKind.COND] / 20000 == pytest.approx(0.1, abs=0.01)


def test_zipfian_skew_and_scramble():
    n = 1000
    spec = WorkloadSpec(num_keys=n, key_dist="zipfian", zipf_theta=0.99)
    s = OpStream(spec, seed=3)
    keys = collections.Counter(s.next_op().key_index for _ in range(30000))
    top = keys.most_common(1)[0][1] / 30000
    # YCSB theta=0.99 over 1000 keys: hottest key ~1/H_n ≈ 13%
    assert 0.08 < top < 0.20
    # scramble spreads the hot ranks: hottest two keys are not adjacent
    (k1, _), (k2, _) = keys.most_common(2)
    assert abs(k1 - k2) > 1
    # uniform has no such skew
    u = OpStream(WorkloadSpec(num_keys=n, key_dist="uniform"), seed=3)
    ukeys = collections.Counter(u.next_op().key_index for _ in range(30000))
    assert ukeys.most_common(1)[0][1] / 30000 < 0.01
    assert all(0 <= k < n for k in keys)


def test_latest_distribution_tracks_horizon():
    spec = WorkloadSpec(num_keys=1000, key_dist="latest")
    s = OpStream(spec, seed=0)
    keys = [s.next_op().key_index for _ in range(5000)]
    # hot keys cluster at the top of the keyspace (most recent inserts)
    assert np.median(keys) > 800
    s.insert_horizon = 100     # pretend only 100 keys inserted so far
    keys2 = [s.next_op().key_index for _ in range(5000)]
    assert max(keys2) <= 99


def test_value_size_distributions():
    fixed = OpStream(WorkloadSpec(num_keys=10, value_size=777), seed=0)
    assert {fixed.next_op().value_size for _ in range(100)} == {777}
    uni = OpStream(WorkloadSpec(num_keys=10, value_size=4096,
                                value_size_dist="uniform",
                                value_size_min=100), seed=0)
    sizes = [uni.next_op().value_size for _ in range(2000)]
    assert min(sizes) >= 100 and max(sizes) <= 4096
    assert len(set(sizes)) > 100


def test_coprime_multiplier_bijective():
    for n in (2, 10, 97, 1000, 4096):
        a = _coprime_multiplier(n)
        assert len({(i * a) % n for i in range(n)}) == n


def test_poisson_gaps_mean():
    s = OpStream(WorkloadSpec(num_keys=10), seed=1)
    gaps = []
    for _ in range(5000):
        gaps.append(s.next_gap(rate=100.0))
        s.next_op()
    assert np.mean(gaps) == pytest.approx(1 / 100.0, rel=0.1)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_percentiles_bounded_error():
    h = LatencyHistogram()
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-7, sigma=1.0, size=20000)
    for x in xs:
        h.add(float(x))
    for p in (50, 95, 99):
        exact = float(np.percentile(xs, p))
        assert h.percentile(p) == pytest.approx(exact, rel=0.10)
    assert h.summary()["count"] == 20000


def test_histogram_percentile_within_one_log_bin():
    # the bin grid is 240/decade: any percentile answer must sit within
    # one bin-width factor (10^(1/240) ~ 1.0096x) of the exact sample
    # quantile, clamped to the observed [min, max]
    h = LatencyHistogram()
    rng = np.random.default_rng(7)
    xs = np.sort(rng.lognormal(mean=-6, sigma=1.5, size=50000))
    for x in xs:
        h.add(float(x))
    bin_factor = 10 ** (1 / 240)
    for p in (10, 50, 90, 95, 99, 99.9):
        exact = float(np.percentile(xs, p, method="inverted_cdf"))
        got = h.percentile(p)
        assert exact / bin_factor * 0.999 <= got <= exact * bin_factor \
            * 1.001, (p, got, exact)


def test_histogram_separates_close_percentiles():
    """Regression for the coarse-bin collapse: a 30/decade grid (~8%
    bins) folded latencies a few percent apart into one bin, so p50, p95
    and p99 of a tight distribution all read back as the same edge value
    (visible as bit-identical percentiles across unrelated runs).  The
    240/decade grid (<1% bins) must keep 5%-apart percentiles distinct,
    ordered, and within 1% of their true values."""
    h = LatencyHistogram()
    for _ in range(5000):
        h.add(1.00e-3)
    for _ in range(4500):
        h.add(1.05e-3)
    for _ in range(500):
        h.add(1.10e-3)
    p50, p95, p99 = (h.percentile(p) for p in (50, 95, 99))
    assert p50 < p95 < p99, (p50, p95, p99)
    assert p50 == pytest.approx(1.00e-3, rel=0.01)
    assert p95 == pytest.approx(1.05e-3, rel=0.01)
    assert p99 == pytest.approx(1.10e-3, rel=0.01)


def test_histogram_empty_summary():
    h = LatencyHistogram()
    s = h.summary()
    assert s["count"] == 0
    for k in ("mean_ms", "p50_ms", "p95_ms", "p99_ms", "min_ms"):
        assert math.isnan(s[k]), (k, s[k])
    assert s["max_ms"] == 0.0


def test_histogram_merge():
    a, b, ref = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    rng = np.random.default_rng(1)
    xa = rng.lognormal(-7, 1.0, 5000)
    xb = rng.lognormal(-5, 0.5, 3000)
    for x in xa:
        a.add(float(x))
        ref.add(float(x))
    for x in xb:
        b.add(float(x))
        ref.add(float(x))
    # merging an empty histogram is the identity
    before = (a.total, a.sum, a.min, a.max, a.percentile(50))
    a.merge(LatencyHistogram())
    assert (a.total, a.sum, a.min, a.max, a.percentile(50)) == before
    # empty.merge(populated) adopts the populated stats wholesale
    e = LatencyHistogram()
    e.merge(b)
    assert e.total == b.total and e.percentile(95) == b.percentile(95)
    assert e.min == b.min and e.max == b.max
    # populated merge: identical to having added both populations
    a.merge(b)
    assert a.total == ref.total
    assert a.sum == pytest.approx(ref.sum)
    assert (a.min, a.max) == (ref.min, ref.max)
    for p in (50, 95, 99):
        assert a.percentile(p) == ref.percentile(p)


def test_oplog_windows():
    log = OpLog()
    for i in range(100):
        log.record(t_done=i * 0.01, kind="read", ok=(i % 10 != 0),
                   latency=0.001)
    ws = log.windows(0.5, kind="read", t0=0.0, t1=1.0)
    assert len(ws) == 2
    assert ws[0].throughput == pytest.approx(90.0, rel=0.15)
    assert 0.0 < ws[0].error_rate < 0.2


def test_oplog_final_window_clamped_to_t1():
    # 100 ops at a steady 100/s; a 0.4s window grid over [0, 1.0) leaves
    # a 0.2s tail, which must report the true 100/s, not half of it
    log = OpLog()
    for i in range(100):
        log.record(t_done=i * 0.01, kind="write", ok=True, latency=0.001)
    ws = log.windows(0.4, kind="write", t0=0.0, t1=1.0)
    assert len(ws) == 3
    assert ws[-1].t_end == pytest.approx(1.0)
    assert ws[-1].t_end - ws[-1].t_start == pytest.approx(0.2)
    for w in ws:
        assert w.throughput == pytest.approx(100.0)


def test_oplog_vectorized_count():
    log = OpLog()
    assert log.count() == 0 and log.count(kind="nope") == 0
    # push past the initial 1024 capacity to exercise array growth
    for i in range(3000):
        kind = ("read", "write", "rmw")[i % 3]
        log.record(t_done=i * 1e-3, kind=kind, ok=(i % 5 != 0),
                   latency=1e-4)
    assert len(log) == 3000
    assert log.count() == 3000
    assert log.count(kind="read") == 1000
    assert log.count(kind="write", ok=True) == 800
    assert log.count(kind="write", ok=False) == 200
    assert log.count(ok=False) == 600
    assert log.count(kind="unknown") == 0
    assert log.count(kind="unknown", ok=True) == 0


# ---------------------------------------------------------------------------
# scenario DSL
# ---------------------------------------------------------------------------


def test_parse_schedule_full_grammar():
    sched = parse_schedule("""
        # comment line
        at 1s crash node 2 lose_disk
        at 2.5s crash leader of 3 no_expire
        at 3s restart node 2
        at 4s restart crashed
        at 5s partition {0,1} | {2,3,4}
        at 6s heal
    """)
    acts = [e.action for e in sched.events]
    assert acts == ["crash", "crash_leader", "restart", "restart",
                    "partition", "heal"]
    assert sched.events[0].lose_disk and sched.events[0].expire_session
    assert not sched.events[1].expire_session
    assert sched.events[3].node is None          # 'restart crashed'
    assert sched.events[4].groups == ((0, 1), (2, 3, 4))


@pytest.mark.parametrize("bad", [
    "at crash node 1",
    "at 1s explode node 1",
    "at 1s crash node 1 gently",
    "at 1s partition {0,1}",
])
def test_parse_schedule_rejects_garbage(bad):
    with pytest.raises(ValueError):
        parse_schedule(bad)


def test_partition_blocks_cross_group_only():
    sim = Simulator(seed=0)
    net = Network(sim)
    got = []
    net.set_partition([{0, 1}, {2}])
    net.send(0, 1, lambda: got.append("01"))
    net.send(0, 2, lambda: got.append("02"))
    net.send(2, 1, lambda: got.append("21"))
    net.send("client", 2, lambda: got.append("c2"))   # ungrouped endpoint
    sim.run_until_idle()
    assert sorted(got) == ["01", "c2"]
    net.clear_partition()
    net.send(0, 2, lambda: got.append("02b"))
    sim.run_until_idle()
    assert "02b" in got


def test_partition_cuts_in_flight_messages():
    sim = Simulator(seed=0)
    net = Network(sim)
    got = []
    net.send(0, 2, lambda: got.append("d"))   # in flight ...
    net.set_partition([{0}, {2}])             # ... cut before delivery
    sim.run_until_idle()
    assert got == []


# ---------------------------------------------------------------------------
# drivers against a live cluster
# ---------------------------------------------------------------------------


def test_closed_loop_driver_records_ops():
    sim, cluster = make_cluster()
    stream = OpStream(WorkloadSpec(num_keys=50, value_size=128), seed=0)
    log = OpLog()
    drv = ClosedLoopDriver(sim, SpinnakerAdapter(cluster.make_client()),
                           stream, log, n_clients=4)
    drv.run(duration=1.0, warmup=0.2)
    assert len(log) > 100
    assert log.count(ok=False) == 0
    assert "read" in log.hists and log.hists["read"].mean > 0


def test_open_loop_driver_hits_target_rate():
    sim, cluster = make_cluster()
    stream = OpStream(WorkloadSpec(num_keys=50, value_size=128), seed=0)
    log = OpLog()
    drv = OpenLoopDriver(sim, SpinnakerAdapter(cluster.make_client()),
                         stream, log, rate=500.0)
    drv.run(duration=2.0, warmup=0.2)
    assert log.count(ok=True) / 2.0 == pytest.approx(500.0, rel=0.15)


def test_multi_get_batched_reads():
    sim, cluster = make_cluster()
    c = cluster.make_client()
    for i in range(8):
        c.sync_put(key_of(i), "c", f"v{i}".encode())
    box = []
    c.multi_get([(key_of(i), "c") for i in range(8)], True,
                lambda rs: box.append(rs))
    sim.run_for(1.0)
    assert box and len(box[0]) == 8
    assert all(r.ok for r in box[0])
    assert [r.value for r in box[0]] == [f"v{i}".encode() for i in range(8)]
    # batched latency ≈ one round trip, not eight: cheaper than serial gets
    assert all(r.latency < 0.02 for r in box[0])


def test_client_latency_tagging_hooks():
    sim, cluster = make_cluster()
    c = cluster.make_client()
    seen = []
    c.op_hook = lambda kind, res: seen.append((kind, res.ok))
    c.sync_put(key_of(1), "c", b"x")
    c.sync_get(key_of(1), "c")
    assert seen.count(("write", True)) == 1
    assert seen.count(("read", True)) == 1
    assert len(seen) == 2


# ---------------------------------------------------------------------------
# failover scenarios (Figs. 9-10)
# ---------------------------------------------------------------------------


def test_writes_resume_after_leader_crash_scenario():
    cfg = ExperimentConfig(duration=6.0, warmup=0.5, n_clients=4,
                           disk="mem", preload_cap=50, window=0.5)
    spec = WorkloadSpec(num_keys=50, value_size=256, read_frac=0.2,
                        write_frac=0.8, rmw_frac=0.0, cond_frac=0.0)
    r = run_spinnaker_workload(
        spec, cfg, schedule="at 1.0s crash leader of 0\n"
                            "at 4.5s restart crashed")
    assert any(e.startswith("t=1.0: crash node") for e in r["fault_events"])
    post = [w for w in r["timeline"]["write"] if w["t_start"] > 1.0]
    assert max(w["throughput"] for w in post) > 0, \
        "writes never resumed after the leader crash"


def test_timeline_reads_monotonic_across_leader_failover():
    """Satellite: a monotonic timeline-read client must never observe the
    version of a key go backwards while the fault schedule kills and
    restarts the leader serving it (PNUTS-style session guarantee)."""
    sim, cluster = make_cluster()
    key = key_of(7)
    rid = cluster.range_of(key)
    writer = cluster.make_client("writer")
    reader = cluster.make_client("reader")

    versions = []

    def keep_writing(i=0):
        if sim.now > 12.0:
            return
        writer.put(key, "c", f"v{i}".encode(),
                   lambda r: sim.schedule(0.01, keep_writing, i + 1))

    def keep_reading():
        if sim.now > 12.0:
            return
        def got(res):
            if res.ok and res.version is not None:
                versions.append(res.version)
            sim.schedule(0.005, keep_reading)
        reader.get(key, "c", consistent=False, cb=got, monotonic=True)

    sched = parse_schedule(f"""
        at 2.0s crash leader of {rid}
        at 6.0s restart crashed
        at 8.0s crash leader of {rid}
        at 10.0s restart crashed
    """)
    sched.install(sim, cluster)
    keep_writing()
    keep_reading()
    sim.run(until=13.0)

    assert len(versions) > 200, "reader starved during failover"
    diffs = np.diff(versions)
    assert (diffs >= 0).all(), \
        f"timeline monotonicity violated at {np.argmin(diffs)}"
    # versions actually advanced across both failovers (writes resumed)
    assert versions[-1] > versions[0] + 100
    assert len(sched.applied) == 4


def test_compile_cache_dir_is_fixed_unless_set(monkeypatch, tmp_path):
    import jax

    from repro.compile_cache import REPO_ROOT, use_compile_cache
    assert (REPO_ROOT / "src" / "repro" / "compile_cache.py").is_file()
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert use_compile_cache() == REPO_ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == \
            str(REPO_ROOT / ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == tmp_path
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
