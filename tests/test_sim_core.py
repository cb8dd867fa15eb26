"""The simulator's event core (core/sim.py) on its own.

- random interleavings of `schedule`, `at` and `cancel`, with many equal
  times, cancels from inside handlers and cancels of entries that already
  ran, run the surviving callbacks exactly as a plain-list reference loop
  does, in strictly increasing (time, seq) order, under `run`, the
  profiled loop and `step`;
- a cancelled event lets go of its callback and arguments at once, long
  before its time comes;
- a short span of the section-9 deployment completes the same ops and
  events, to the same clock and p99, as recorded before the heap entries
  became lists: the event order is unchanged.
"""

import gc
import random
import weakref

import numpy as np
import pytest

from repro.core import key_of
from repro.core.sim import Event, Simulator
from repro.obs.hostprof import HostProfile
from repro.workload.drivers import ClosedLoopDriver, SpinnakerAdapter
from repro.workload.experiment import ExperimentConfig, build_spinnaker
from repro.workload.generators import OpStream, WorkloadSpec
from repro.workload.metrics import OpLog

# quarter steps are exact in binary, so sums of them tie exactly
DELAYS = (0.0, 0.0, 0.25, 0.5, 0.5, 1.0)
TIMES = tuple(0.25 * i for i in range(13))
MAX_EVENTS = 400


class _RefEvent:
    def __init__(self, loop, time, order, fn, args):
        self.loop, self.key, self.fn, self.args = loop, (time, order), fn, args

    def cancel(self):
        if self in self.loop.pending:
            self.loop.pending.remove(self)


class ListLoop:
    """The reference: pending callbacks in a plain list, the earliest by
    (time, order of scheduling) found by a scan, a cancel removing it."""

    def __init__(self):
        self.now = 0.0
        self.pending = []
        self.n = 0

    def schedule(self, delay, fn, *args):
        ev = _RefEvent(self, self.now + delay, self.n, fn, args)
        self.n += 1
        self.pending.append(ev)
        return ev

    def at(self, time, fn, *args):
        return self.schedule(max(0.0, time - self.now), fn, *args)

    def _next(self):
        return min(self.pending, key=lambda e: e.key) \
            if self.pending else None

    def _fire(self, ev):
        self.pending.remove(ev)
        self.now = max(self.now, ev.key[0])
        ev.fn(*ev.args)

    def run(self, until=None):
        while (ev := self._next()) is not None:
            if until is not None and ev.key[0] > until:
                self.now = until
                return
            self._fire(ev)
        if until is not None:
            self.now = max(self.now, until)

    def step(self):
        ev = self._next()
        if ev is None:
            return False
        self._fire(ev)
        return True


def _scenario(loop, seed, drive):
    """A random program of schedules, `at`s and cancels, at the top level
    and inside handlers; returns the (time, tag) of every callback that
    ran, in order.  A tag is the callback's order of scheduling."""
    rng = random.Random(seed)
    handles, ran = [], []

    def handler(tag, *_payload):
        ran.append((loop.now, tag))
        for _ in range(rng.choice((0, 0, 1, 1, 2, 3))):
            act()

    def act():
        r = rng.random()
        if r < 0.45 and len(handles) < MAX_EVENTS:
            handles.append(loop.schedule(rng.choice(DELAYS), handler,
                                         len(handles), object()))
        elif r < 0.7 and len(handles) < MAX_EVENTS:
            # times in the past are clamped to now
            handles.append(loop.at(rng.choice(TIMES), handler, len(handles)))
        elif handles:
            # pending, already run or already cancelled, or the running
            # handler's own entry
            rng.choice(handles).cancel()

    for _ in range(80):
        act()
    drive(loop, rng)
    return ran


def _slices(loop, rng):
    until = 0.0
    while until < 4.0:
        until += rng.choice((0.0, 0.25, 0.3, 0.5))
        loop.run(until=until)
    loop.run()


def _profiled_slices(sim, rng):
    hp = HostProfile().start(sim)
    _slices(sim, rng)
    hp.stop()
    assert hp.pops >= hp.cancelled_pops > 0


def _steps(loop, rng):
    while loop.step():
        pass


@pytest.mark.parametrize("drive", [_slices, _profiled_slices, _steps],
                         ids=["run", "profiled", "step"])
@pytest.mark.parametrize("seed", range(8))
def test_random_interleavings_run_in_time_seq_order(seed, drive):
    sim = Simulator(seed=0)
    ran = _scenario(sim, seed, drive)
    ref = _scenario(ListLoop(), seed,
                    _slices if drive is _profiled_slices else drive)
    assert len(ran) > 30
    assert ran == ref
    # a tag is the entry's seq: the run order is sorted (time, seq)
    assert ran == sorted(set(ran))
    assert not sim._heap


def _held(sim, owner, where):
    """Schedule an event at t=5 whose callback (`closure`) or arguments
    (`args`) alone hold a fresh payload; returns the event and a weak
    reference to the payload."""
    class Payload:
        pass
    payload = Payload()
    ref = weakref.ref(payload)
    if where == "closure":
        ev = sim.at(5.0, lambda: owner.append(payload))
    else:
        ev = sim.at(5.0, owner.append, payload)
    return ev, ref


@pytest.mark.parametrize("where", ["closure", "args"])
def test_cancel_frees_the_callback_at_once(where):
    sim = Simulator()
    owner = []
    ev, ref = _held(sim, owner, where)
    gc.disable()
    try:
        assert ref() is not None
        ev.cancel()
        assert ref() is None
    finally:
        gc.enable()
    assert ev.cancelled and ev.time == 5.0 and len(sim._heap) == 1
    sim.run()
    # a cancelled entry pops without moving the clock
    assert owner == [] and not sim._heap and sim.now == 0.0


@pytest.mark.parametrize("profiled", [False, True])
@pytest.mark.parametrize("where", ["closure", "args"])
def test_cancel_from_a_handler_frees_the_callback_before_its_time(
        where, profiled):
    sim = Simulator()
    owner, seen = [], []
    ev, ref = _held(sim, owner, where)
    sim.at(1.0, ev.cancel)
    sim.at(2.0, lambda: seen.append((sim.now, ref() is None)))
    hp = HostProfile().start(sim) if profiled else None
    gc.disable()
    try:
        sim.run()
    finally:
        gc.enable()
    if hp is not None:
        hp.stop()
        assert hp.cancelled_pops == 1
    assert seen == [(2.0, True)] and owner == [] and sim.now == 2.0


def test_entry_is_ordered_by_the_list_comparison():
    assert "__lt__" not in Event.__dict__
    sim = Simulator()
    a = sim.schedule(1.0, print)
    b = sim.schedule(1.0, print)
    assert a == [1.0, 0, print, ()] and a < b
    assert a.time == 1.0 and not a.cancelled
    b.cancel()
    assert b.cancelled and b == [1.0, 1, None, ()] and a < b


# -- trajectory pin ----------------------------------------------------------

S9_KEYS = 5000
PIN_SEED = 2000000011


def test_s9_span_matches_the_recorded_trajectory():
    """The section-9 deployment (5 nodes, ssd, 40 ranges, 5,000 keys of
    4 KB preloaded, zipfian 0.99, 80/15/3/2 mix, 32 closed-loop clients,
    strong reads), 0.25 sim-s of warm-up, then 0.8 sim-s recorded.  The
    span reaches past the preload's 1-s attempt timeouts, which the client
    cancelled, so the loop skips thousands of cancelled entries.  The
    constants were recorded with the earlier heap entries, ordered by a
    Python `__lt__` on (time, seq)."""
    cfg = ExperimentConfig(
        n_nodes=5, disk="ssd", seed=PIN_SEED, commit_period=0.05,
        batch="adaptive", batch_max_records=32, batch_deadline=0.0005,
        ingress_batch=True, admission_limit=None, ranges_per_node=8,
        lease_enabled=True, lease_duration=1.0, trace_sample=0.0,
        metrics_interval=0.0, profile=False, profile_interval=0.0,
        journal=False)
    sim, cluster = build_spinnaker(cfg, num_keys=S9_KEYS)
    loader = cluster.make_client("preload")
    done = []
    for i in range(S9_KEYS):
        loader.put(key_of(i), "c", b"x" * 4096,
                   lambda r: done.append(r.ok))
    while len(done) < S9_KEYS:
        sim.run(until=sim.now + 0.25)
    assert all(done) and sim.now == 0.3
    spec = WorkloadSpec(num_keys=S9_KEYS, key_dist="zipfian",
                        zipf_theta=0.99, scramble=True, read_frac=0.80,
                        write_frac=0.15, rmw_frac=0.03, cond_frac=0.02,
                        value_size=4096, value_size_dist="fixed")
    log = OpLog()
    drv = ClosedLoopDriver(sim, SpinnakerAdapter(cluster.make_client("bench")),
                           OpStream(spec, seed=PIN_SEED), log, n_clients=32)
    drv.run(0.8, warmup=0.25)
    n = len(log)
    assert (n, log.count(ok=True)) == (36277, 36277)
    assert sim.events_processed == 352513
    assert sim.now == 1.35
    assert float(np.percentile(log._lat[:n], 99)) == 0.0017540821234973874
    assert len(sim._heap) == 48678
