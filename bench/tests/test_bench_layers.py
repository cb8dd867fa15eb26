"""The stack sampler's module-to-layer attribution."""

import threading
import time

import pytest

from bench import stacks

PKG = "/ck/src/repro"


@pytest.mark.parametrize("filename,layer", [
    (f"{PKG}/core/sim.py", "event_core"),
    (f"{PKG}/core/cluster.py", "client"),
    (f"{PKG}/core/ranges.py", "client"),
    (f"{PKG}/core/replica.py", "protocol"),
    (f"{PKG}/core/wal.py", "protocol"),
    (f"{PKG}/core/storage.py", "protocol"),
    (f"{PKG}/core/txn.py", "protocol"),
    (f"{PKG}/core/types.py", "protocol"),
    (f"{PKG}/core/node.py", "node"),
    (f"{PKG}/core/coordination.py", "node"),
    (f"{PKG}/workload/drivers.py", "workload"),
    (f"{PKG}/workload/generators.py", "workload"),
    (f"{PKG}/obs/events.py", stacks.OTHER),
    (f"{PKG}/chaos/linearizability.py", stacks.OTHER),
    ("/ck/bench/harness.py", None),
    ("/usr/lib/python3.12/heapq.py", None),
    ("/ck/src/repro_extra/core/sim.py", None),
])
def test_layer_of(filename, layer):
    assert stacks.layer_of(filename, PKG) == layer


class _Code:
    def __init__(self, filename):
        self.co_filename = filename


class _Frame:
    def __init__(self, filename, back=None):
        self.f_code, self.f_back = _Code(filename), back


def test_sample_charges_the_innermost_program_frame():
    s = stacks.StackSampler(PKG, thread_id=0)
    harness = _Frame("/ck/bench/harness.py")
    sim = _Frame(f"{PKG}/core/sim.py", harness)
    replica = _Frame(f"{PKG}/core/replica.py", sim)
    lib = _Frame("/usr/lib/python3.12/heapq.py", replica)
    assert s.sample(lib) == "protocol"
    assert s.sample(sim) == "event_core"
    assert s.sample(harness) is None
    assert s.sample(None) is None


def test_sampler_thread_counts_and_stops():
    target = threading.get_ident()
    s = stacks.StackSampler(PKG, target, interval=1e-3)
    s.start()
    time.sleep(0.05)
    s.stop()
    assert sum(s.counts.values()) > 0
    assert set(s.counts) <= {None}


def test_us_per_op():
    class Obs:
        layer_samples = {"event_core": 60, "client": 30, None: 10}
        window_s, ops_ok = 2.0, 4000
    assert stacks.us_per_op(Obs, "event_core") == pytest.approx(300.0)
    assert stacks.us_per_op(Obs, "node") == 0.0
    Obs.ops_ok = 0
    assert stacks.us_per_op(Obs, "client") is None
