"""The network's per-message path (core/sim.py, `Network`).

- against a reference copy of the earlier path (a closure per message
  that checks liveness and partitions again at every delivery), random
  sends under a seeded mix of crashes, symmetric and one-way partitions,
  link drop/dup/slow faults and heals, issued between events and from
  inside delivery handlers while messages are in flight, deliver the same
  messages at the same times, drop the same ones, count the same, and
  leave the simulator's random stream in the same state;
- `delivery_rechecks` counts exactly the deliveries during whose flight a
  fault changed;
- a delivery event's callback is the network's one bound method, and a
  delivered message's arguments are let go once its event has run.
"""

import gc
import random
import weakref

import pytest

from repro.core.sim import Network, Simulator
from repro.obs.hostprof import HostProfile

ENDPOINTS = (0, 1, 2, 3, 4, "client")
NODES = ENDPOINTS[:5]


class RefNetwork(Network):
    """The earlier send and delivery: a `deliver` closure per copy, which
    checks `_blocked` again whatever happened in flight.  It also counts
    the deliveries during whose flight a fault changed (`crossed`)."""

    def __init__(self, sim):
        super().__init__(sim)
        self.changes = 0
        self.crossed = 0

    def set_down(self, endpoint, down=True):
        self.changes += 1
        super().set_down(endpoint, down)

    def set_partition(self, groups):
        self.changes += 1
        super().set_partition(groups)

    def clear_partition(self):
        self.changes += 1
        super().clear_partition()

    def set_oneway_partition(self, src_group, dst_group):
        self.changes += 1
        super().set_oneway_partition(src_group, dst_group)

    def clear_oneway_partitions(self):
        self.changes += 1
        super().clear_oneway_partitions()

    def _blocked(self, src, dst):
        return src in self._down or dst in self._down \
            or self.partitioned(src, dst)

    def send(self, src, dst, handler, *args, nbytes=256, cross_switch=False,
             component=None, rid=None):
        if self._blocked(src, dst):
            self.dropped += 1
            return
        fault = self._link_faults.get((src, dst))
        copies = 1
        delay_factor = 1.0
        if fault is not None:
            drop_p, dup_p, delay_factor = fault
            if drop_p and self.sim.rng.random() < drop_p:
                self.dropped += 1
                return
            if dup_p and self.sim.rng.random() < dup_p:
                copies = 2
        link = (src, dst)
        last = self._last_send.get(link)
        warm = last is not None \
            and self.sim.now - last <= self.p.stream_idle
        self._last_send[link] = self.sim.now
        overhead = self.p.stream_floor if warm else self.p.base_latency
        if warm:
            self.msgs_warm += 1
        for _ in range(copies):
            lat = self.sim.jitter(overhead, self.p.jitter_cv)
            lat += nbytes / self.p.bandwidth
            if cross_switch:
                lat += self.p.cross_switch_extra
            lat *= delay_factor
            key = (src, dst)
            deliver_at = max(self.sim.now + lat,
                             self._last_delivery.get(key, 0.0) + 1e-9)
            self._last_delivery[key] = deliver_at
            self.bytes_sent += nbytes
            self.msgs_sent += 1
            changes = self.changes

            def deliver():
                if self.changes != changes:
                    self.crossed += 1
                if self._blocked(src, dst):
                    self.dropped += 1
                    return
                handler(*args)

            self.sim.at(deliver_at, deliver)


class Chaos:
    """Random sends and fault changes from a generator of its own, so two
    networks that behave alike see the same choices in the same order."""

    def __init__(self, sim, net, seed):
        self.sim, self.net = sim, net
        self.rng = random.Random(seed)
        self.log = []          # (time, message id, copy seen) per delivery
        self.seen = {}
        self.n = 0

    def send(self):
        r = self.rng
        src, dst = r.sample(ENDPOINTS, 2)
        mid = self.n
        self.n += 1
        self.net.send(src, dst, self.on_message, mid,
                      nbytes=r.choice((0, 64, 256, 4096, 65536)),
                      cross_switch=r.random() < 0.3)

    def fault(self):
        r, net = self.rng, self.net
        kind = r.randrange(9)
        if kind == 0:
            net.set_down(r.choice(NODES), r.random() < 0.3)
        elif kind == 1:
            cut = r.randrange(1, len(NODES))
            nodes = r.sample(NODES, len(NODES))
            net.set_partition([set(nodes[:cut]), set(nodes[cut:])])
        elif kind == 2:
            net.clear_partition()
        elif kind == 3:
            a, b = r.sample(NODES, 2)
            net.set_oneway_partition({a}, {b, "client"})
        elif kind == 4:
            net.clear_oneway_partitions()
        elif kind == 5:
            src, dst = r.sample(ENDPOINTS, 2)
            net.set_link_fault(src, dst, drop_p=r.choice((0.0, 0.3)),
                               dup_p=r.choice((0.0, 0.5)),
                               delay_factor=r.choice((1.0, 4.0)))
        elif kind == 6:
            src, dst = r.sample(ENDPOINTS, 2)
            net.update_link_fault(src, dst, delay_factor=8.0)
        elif kind == 7:
            src, dst = r.sample(ENDPOINTS, 2)
            net.clear_link_fault(src, dst)
        else:
            net.clear_faults()

    def act(self, fault_p):
        if self.rng.random() < fault_p:
            self.fault()
        for _ in range(self.rng.randrange(4)):
            self.send()

    def on_message(self, mid):
        copy = self.seen.get(mid, 0)
        self.seen[mid] = copy + 1
        self.log.append((self.sim.now, mid, copy))
        self.act(0.03)

    def tick(self):
        self.act(0.2)

    def run(self, ticks):
        for i in range(ticks):
            # ticks a few message latencies apart, so faults land in flight
            self.sim.schedule(i * 150e-6, self.tick)
        for _ in range(20):
            self.send()
        self.sim.run(until=ticks * 150e-6 + 0.5)
        return self


def _run(net_cls, seed, ticks=400, profiled=False):
    sim = Simulator(seed=seed)
    net = net_cls(sim)
    hp = HostProfile().start(sim, net) if profiled else None
    chaos = Chaos(sim, net, seed + 1).run(ticks)
    if hp is not None:
        hp.stop()
    return chaos


@pytest.mark.parametrize("seed,profiled", [
    (1, False), (2, False), (3, False), (2147483659, False), (4, True)])
def test_same_deliveries_drops_counts_and_random_stream(seed, profiled):
    ref = _run(RefNetwork, seed)
    new = _run(Network, seed, profiled=profiled)
    assert len(ref.log) > 500 and ref.net.dropped > 50
    assert sum(c for _t, _m, c in ref.log) > 0          # duplicates came
    assert new.log == ref.log
    for counter in ("dropped", "msgs_sent", "bytes_sent", "msgs_warm"):
        assert getattr(new.net, counter) == getattr(ref.net, counter), \
            counter
    assert new.sim.rng.getstate() == ref.sim.rng.getstate()
    assert new.sim.events_processed == ref.sim.events_processed
    assert new.sim.now == ref.sim.now


@pytest.mark.parametrize("seed", [5, 6, 2147483693])
def test_rechecks_count_the_deliveries_that_crossed_a_fault_change(seed):
    ref = _run(RefNetwork, seed)
    new = _run(Network, seed)
    assert 0 < ref.net.crossed < ref.net.msgs_sent
    assert new.net.delivery_rechecks == ref.net.crossed


def test_no_recheck_while_no_fault_changes():
    sim = Simulator(seed=7)
    net = Network(sim)
    net.set_down(4)                    # before any message is in flight
    got = []
    for i in range(200):
        net.send(i % 4, (i + 1) % 4, got.append, i)
        net.send(i % 4, 4, got.append, -1)          # to the down node
    sim.run()
    assert sorted(got) == list(range(200))
    assert net.dropped == 200 and net.delivery_rechecks == 0


def test_a_message_in_flight_across_a_crash_is_rechecked_and_dropped():
    sim = Simulator(seed=8)
    net = Network(sim)
    got = []
    net.send(0, 1, got.append, "cut")
    net.send(0, 2, got.append, "kept")
    net.set_down(1)
    sim.run()
    assert got == ["kept"]
    assert net.dropped == 1 and net.delivery_rechecks == 2


def test_the_delivery_callback_is_one_bound_method():
    sim = Simulator(seed=9)
    net = Network(sim)
    for i in range(10):
        net.send(i % 3, 3, print, i, nbytes=100 * i)
    fns = {id(ev[2]) for ev in sim._heap}
    assert fns == {id(net._deliver_cb)}
    assert net._deliver_cb.__func__ is Network._deliver


class Payload:
    pass


@pytest.mark.parametrize("profiled", [False, True])
def test_a_delivered_message_lets_go_of_its_arguments(profiled):
    sim = Simulator(seed=10)
    net = Network(sim)
    hp = HostProfile().start(sim, net) if profiled else None
    payload = Payload()
    ref = weakref.ref(payload)
    got = []
    net.send(0, 1, lambda p: got.append(sim.now), payload)
    del payload
    seen = []
    gc.disable()
    try:
        sim.schedule(0.5, lambda: seen.append(ref() is None))
        sim.run()
    finally:
        gc.enable()
        if hp is not None:
            hp.stop()
    assert len(got) == 1 and got[0] < 0.5
    assert seen == [True]
