"""The wall-clock host profile (obs/hostprof.py).

- the layers' self times and `outside` add up to the recorder's wall
  exactly, and the per-handler event counts to the events it saw;
- a run with the recorder on is op-for-op identical to one with it off;
- every handler a section-9-shaped run dispatches has a named layer;
- each sampler refill opens one trace annotation carrying the layers'
  self times, and the sampler's draws carry stable named scopes.
"""

import gc
import re
from pathlib import Path

import jax
import pytest

from repro.core import key_of
from repro.core.node import COMPONENT_OF
from repro.core.sim import Network, Simulator
from repro.obs import hostprof
from repro.obs.hostprof import HostProfile
from repro.workload.drivers import ClosedLoopDriver, SpinnakerAdapter
from repro.workload.experiment import ExperimentConfig, build_spinnaker
from repro.workload.generators import OpStream, WorkloadSpec, _sample_batch
from repro.workload.metrics import LatencyHistogram, OpLog

SPEC = WorkloadSpec(num_keys=200, value_size=512)   # the 80/15/3/2 mix


def _cluster(n_nodes=5, seed=3, ranges_per_node=4):
    cfg = ExperimentConfig(n_nodes=n_nodes, disk="ssd", seed=seed,
                           ranges_per_node=ranges_per_node,
                           trace_sample=0.0, profile=False, journal=False)
    return build_spinnaker(cfg, num_keys=SPEC.num_keys)


def _drive(sim, cluster, n_clients=16, batch=256, seed=5):
    """Preload the keys, then start closed-loop clients on the mix."""
    loader = cluster.make_client("preload")
    done = []
    for i in range(SPEC.num_keys):
        loader.put(key_of(i), "c", b"x" * 64, done.append)
    while len(done) < SPEC.num_keys:
        sim.run(until=sim.now + 0.1)
    results = []
    client = cluster.make_client("bench")
    client.op_hook = lambda kind, res: results.append(
        (round(sim.now, 9), kind, res.code, res.version))
    stream = OpStream(SPEC, seed=seed, batch=batch)
    drv = ClosedLoopDriver(sim, SpinnakerAdapter(client), stream, OpLog(),
                           n_clients=n_clients)
    drv._t_end = 1e9
    for _ in range(n_clients):
        drv._loop(sim.now)
    return stream, results


def _profiled(sim, cluster, span, slices=10):
    hp = HostProfile().start(sim, cluster.net,
                             [n.disk for n in cluster.nodes.values()])
    ev0 = sim.events_processed
    for _ in range(slices):
        sim.run(until=sim.now + span / slices)
    hp.stop()
    return hp, sim.events_processed - ev0


@pytest.fixture(scope="module")
def s9_run():
    sim, cluster = _cluster()
    stream, _results = _drive(sim, cluster)
    sim.run(until=sim.now + 0.05)                 # warm up
    hp, events = _profiled(sim, cluster, 0.1)
    return hp, events, stream


def test_self_times_telescope_to_the_wall_exactly(s9_run):
    hp, _events, _stream = s9_run
    s = hp.summary()
    assert sum(s["self_ns"].values()) == s["wall_ns"] > 0
    assert tuple(s["self_ns"]) == hostprof.COLUMNS
    assert s["self_ns"]["outside"] > 0           # between the slices
    assert hp._sim.hostprof is None


def test_handler_counts_sum_to_events_processed(s9_run):
    hp, events, _stream = s9_run
    s = hp.summary()
    assert events > 1000
    assert s["events"] == events
    assert sum(r[2] for r in hp._handlers.values()) == events
    assert s["pops"] == events + s["cancelled_pops"]
    assert 0 < s["heap_depth_sum"] / s["pops"] <= s["heap_depth_max"]
    # protocol messages received, by the node's component labels
    assert set(s["msgs_by_component"]) <= set(COMPONENT_OF.values())
    assert 0 < sum(s["msgs_by_component"].values()) < s["msgs_sent"]
    assert s["disk_forces"] > 0 and s["bytes_sent"] > 0
    assert 0 < s["msgs_warm"] <= s["msgs_sent"]
    # no fault changed, so no delivery checked liveness again
    assert s["delivery_rechecks"] == 0


def test_every_dispatched_handler_has_a_named_layer(s9_run):
    hp, _events, _stream = s9_run
    s = hp.summary()
    unnamed = [r[1] for r in hp._handlers.values()
               if r[2] and r[0] == hostprof.OTHER]
    assert unnamed == []
    assert s["self_ns"]["other"] < 0.01 * s["wall_ns"]
    for layer in ("sched", "net", "queues", "node", "protocol", "client",
                  "workload", "sampler_wait"):
        assert s["self_ns"][layer] > 0, layer
    names = dict(s["handlers_by_count"])
    # deliveries and CPU completions count under the callback they ran
    assert not any(n.startswith(("Network.", "FifoServer.")) for n in names)
    assert "Disk._start_batch.<locals>.done" in names
    assert "SpinnakerNode.handle_client" in names
    assert s["sampler_batches"] >= 1


@pytest.mark.parametrize("filename,qualname,layer", [
    ("core/sim.py", "Simulator.run", hostprof.SCHED),
    ("core/sim.py", "Network.send.<locals>.deliver", hostprof.NET),
    ("core/sim.py", "Network._deliver", hostprof.NET),
    ("core/sim.py", "FifoServer.submit.<locals>.fire", hostprof.QUEUES),
    ("core/sim.py", "Disk._start_batch.<locals>.done", hostprof.QUEUES),
    ("core/node.py", "SpinnakerNode.receive", hostprof.NODE),
    ("core/node.py", "SpinnakerNode.receive.<locals>.<lambda>",
     hostprof.PROTOCOL),
    ("core/coordination.py", "Coordination.set", hostprof.NODE),
    ("core/replica.py", "CohortReplica.on_ack", hostprof.PROTOCOL),
    ("core/wal.py", "WAL.force", hostprof.PROTOCOL),
    ("core/cluster.py", "Client._flush_reqs", hostprof.CLIENT),
    ("core/ranges.py", "RangeTable.lookup", hostprof.CLIENT),
    ("workload/drivers.py", "ClosedLoopDriver._loop", hostprof.WORKLOAD),
    ("obs/events.py", "EventLog.emit", hostprof.OTHER),
])
def test_layer_of_module(filename, qualname, layer):
    pkg = str(Path(hostprof.__file__).resolve().parents[1])
    assert hostprof.layer_of(f"{pkg}/{filename}", qualname) == layer
    assert hostprof.layer_of(f"/elsewhere/{filename}", qualname) \
        == hostprof.OTHER


def test_a_delivery_is_charged_to_and_counted_under_its_handler():
    sim = Simulator(seed=4)
    net = Network(sim)
    hist = LatencyHistogram()               # a workload-layer handler
    hp = HostProfile().start(sim, net)
    for i in range(300):
        net.send(i % 3, 3, hist.add, 1e-3 * i)
    sim.run()
    net.set_down(3)
    net.send(0, 3, hist.add, 1.0)            # dropped at send
    net.send(0, 4, hist.add, 2.0)
    net.set_down(1)                          # in flight: rechecked
    sim.run()
    hp.stop()
    s = hp.summary()
    names = dict(s["handlers_by_count"])
    assert names == {"LatencyHistogram.add": 301}
    assert hist.total == 301
    assert s["delivery_rechecks"] == 1 and s["msgs_sent"] == 301
    assert s["self_ns"]["workload"] > 0 and s["self_ns"]["net"] > 0
    assert s["self_ns"]["other"] == 0
    assert sum(s["self_ns"].values()) == s["wall_ns"]


def _fixed_run(profile):
    sim, cluster = _cluster(n_nodes=3, seed=11, ranges_per_node=2)
    stream, results = _drive(sim, cluster, n_clients=8, batch=128)
    hp = None
    for i in range(20):
        if profile and i == 5:
            hp = HostProfile().start(sim, cluster.net)
        sim.run(until=sim.now + 0.005)
    if hp is not None:
        hp.stop()
    stores = {(nid, rid): [(k, c, repr(cell)) for k, c, cell in
                           rep.store.iter_range("", "")]
              for nid, node in cluster.nodes.items()
              for rid, rep in node.replicas.items()}
    return results, sim.events_processed, sim.now, stores, stream.sampled


def test_profiled_run_is_bit_identical():
    off = _fixed_run(False)
    on = _fixed_run(True)
    assert len(off[0]) > 100
    assert on == off


def test_one_trace_annotation_per_refill_with_layer_times(tmp_path):
    sim, cluster = _cluster(n_nodes=3, seed=2, ranges_per_node=2)
    stream, _ = _drive(sim, cluster, n_clients=8, batch=64)
    sim.run(until=sim.now + 0.02)
    out = tmp_path
    jax.profiler.start_trace(str(out))
    hp = HostProfile().start(sim, cluster.net)
    sampled0 = stream.sampled
    sim.run(until=sim.now + 0.03)
    hp.stop()
    jax.profiler.stop_trace()
    refills = (stream.sampled - sampled0) // stream.batch
    assert refills >= 2 and hp.batches == refills
    from jax.profiler import ProfileData
    trace = sorted(out.rglob("*.xplane.pb"))[-1]
    spans = [dict(e.stats) for p in ProfileData.from_file(str(trace)).planes
             for ln in p.lines for e in ln.events
             if e.name == hostprof.BATCH_SPAN]
    assert len(spans) == refills
    want = {f"{k}_us" for k in hostprof.COLUMNS} \
        | {"events", "pops", "cancelled_pops"}
    for meta in spans:
        assert set(meta) == want
    assert sum(m["events"] for m in spans) > 0
    assert all(m["sampler_wait_us"] >= 0 for m in spans)


def test_off_leaves_no_recorder_state():
    sim, cluster = _cluster(n_nodes=3, seed=2, ranges_per_node=2)
    stream, _ = _drive(sim, cluster, n_clients=8, batch=64)
    assert sim.hostprof is None and stream.sim is sim
    sim.run(until=sim.now + 0.02)
    assert sim._staged is None


def test_garbage_collections_are_their_own_layer():
    sim, cluster = _cluster(n_nodes=3, seed=2, ranges_per_node=2)
    sim.schedule(0.001, gc.collect)
    hp = HostProfile().start(sim, cluster.net)
    sim.run(until=sim.now + 0.01)
    hp.stop()
    s = hp.summary()
    assert s["gc_collections"][2] >= 1
    assert s["self_ns"]["gc"] > 0
    assert sum(s["self_ns"].values()) == s["wall_ns"]
    assert hp._collection not in gc.callbacks


def test_first_refill_time_is_kept():
    s = OpStream(WorkloadSpec(num_keys=50), batch=32)
    assert s.first_refill_s is None
    s.next_op()
    first = s.first_refill_s
    assert first is not None and first > 0
    for _ in range(100):
        s.next_op()
    assert s.first_refill_s == first


def test_sampler_draws_carry_named_scopes():
    s = OpStream(WorkloadSpec(num_keys=100), batch=64)
    lowered = _sample_batch.lower(jax.random.PRNGKey(0), s._cdf, s._mix_cdf,
                                  num_keys=100, vfix=4096, vmin=4096,
                                  vmax=4096, batch=64)
    hlo = lowered.compile().as_text()
    # the benchmark's trace reduction finds the sampler by this name
    assert re.search(r"^HloModule jit__sample_batch\b", hlo, re.M)
    scopes = set(re.findall(r'op_name="jit\(_sample_batch\)/(\w+)/', hlo))
    assert {"zipf_search", "op_mix", "value_size", "gaps"} <= scopes
