"""Wall-clock host profile of the simulator's layers.

Everything else in `obs/` measures simulated time.  `HostProfile` measures
where the host's own time goes while the simulator runs: a stack of layers
entered and left at the boundaries where one layer calls into another.
Each transition reads `time.perf_counter_ns()` once and charges the time
since the previous transition to the layer on top, so every layer's self
time comes out directly, and the self times plus `outside` (time between
`start` and `stop` inside no layer) add up to the recorder's wall time
exactly, in integer nanoseconds.

The recorder is reached through one attribute, `Simulator.hostprof`
(None when off).  Each boundary tests it once; nothing else runs while it
is off.  It draws no simulator randomness and schedules no events, so a
run with it on is bit-identical to one with it off.

Layers (`LAYERS`, indexed by the constants below):

- `sched`: the `Simulator`'s loop and heap: pops, cancelled skips, and
  the pushes of the events each handler scheduled;
- `net`: `Network.send`;
- `queues`: `FifoServer.submit`, `Disk.force`, and the disk's completion
  event up to its callbacks;
- `node`: `core/node.py`, `core/coordination.py`;
- `protocol`: `core/replica.py`, `wal.py`, `storage.py`, `txn.py`,
  `types.py`, and the node's thunks that run a replica handler;
- `client`: `core/cluster.py`, `core/ranges.py`;
- `workload`: the load drivers and the op stream's host side;
- `recovery`: a restarted node's local recovery, the catch-up and
  takeover handlers, and the client's re-sends when a range's leader
  changes (entered inside the layer that reaches them);
- `sampler_wait`: from the sampler's dispatch to its outputs being numpy
  arrays on the host;
- `gc`: the interpreter's cyclic garbage collections, wherever they
  interrupt (a full one over the simulator's million objects takes a
  third of a second, which would otherwise land on a random layer);
- `other`: callbacks from any other module (none on the benchmark's
  path).

An event is charged to, and counted under, its callback; a message
delivery or a CPU completion, to the callback it runs.

Once per sampler batch the op stream opens a `jax.profiler`
`TraceAnnotation` named `BATCH_SPAN` whose arguments are `batch_meta()`:
each layer's self time since the previous batch, so the device trace can
split the host time between two sampler runs by layer on its own clock.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any, Callable

LAYERS = ("sched", "net", "queues", "node", "protocol", "client",
          "workload", "recovery", "sampler_wait", "gc", "other")
(SCHED, NET, QUEUES, NODE, PROTOCOL, CLIENT, WORKLOAD, RECOVERY, SAMPLER_WAIT,
 GC, OTHER) = range(len(LAYERS))
OUTSIDE = len(LAYERS)           # index of the time inside no layer
COLUMNS = LAYERS + ("outside",)
BATCH_SPAN = "obs.sampler_batch"

# module of the package -> layer; the first matching prefix wins
LAYER_OF_MODULE = (
    ("core/sim.py", SCHED),
    ("core/cluster.py", CLIENT),
    ("core/ranges.py", CLIENT),
    ("core/replica.py", PROTOCOL),
    ("core/wal.py", PROTOCOL),
    ("core/storage.py", PROTOCOL),
    ("core/txn.py", PROTOCOL),
    ("core/types.py", PROTOCOL),
    ("core/node.py", NODE),
    ("core/coordination.py", NODE),
    ("workload/", WORKLOAD),
)
# the event core's callbacks that run one other callback, an event of
# which is charged to that callback's layer and counted under it: closures,
# with the name of the callback among their free variables, and methods,
# with its position among the event's arguments
CORE_CLOSURES = {"FifoServer.submit.<locals>.fire": "cb"}
CORE_METHODS = {"Network._deliver": 2}
# the node's CPU thunks, which only call a replica handler
PROTOCOL_THUNKS = ("SpinnakerNode.receive.<locals>.<lambda>",
                   "SpinnakerNode.handle_client.<locals>.<lambda>")
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) + "/"
clock = time.perf_counter_ns


def layer_of(filename: str, qualname: str) -> int:
    """Layer of a function, from its module and qualified name."""
    if not filename.startswith(_PACKAGE):
        return OTHER
    rel = filename[len(_PACKAGE):]
    if qualname in PROTOCOL_THUNKS:
        return PROTOCOL
    for module, layer in LAYER_OF_MODULE:
        if rel.startswith(module):
            if layer == SCHED and qualname.startswith("Network."):
                return NET
            if layer == SCHED and qualname.startswith(("FifoServer.",
                                                       "Disk.")):
                return QUEUES
            return layer
    return OTHER


def _see_through(code) -> Callable | None:
    """For an event core callback that runs one other callback, a function
    of the event's callback and arguments that returns that other one."""
    name = code.co_qualname
    if name in CORE_CLOSURES:
        cell = code.co_freevars.index(CORE_CLOSURES[name])
        return lambda fn, args: fn.__closure__[cell].cell_contents
    if name in CORE_METHODS:
        pos = CORE_METHODS[name]
        return lambda fn, args: args[pos]
    return None


class HostProfile:
    """Self time per layer, event counts per handler and heap counters,
    between `start` and `stop`."""

    def __init__(self):
        self.ns = [0] * (len(LAYERS) + 1)      # self ns per layer, outside
        self.pops = 0
        self.cancelled_pops = 0
        self.depth_sum = 0
        self.depth_max = 0
        self.msgs: dict[str, int] = {}         # component label -> messages
        self.batches = 0
        self.gc_collections = [0, 0, 0]        # per generation
        self._top = OUTSIDE
        self._stack: list[int] = []
        self._t = 0
        self._t0 = self._t1 = 0
        # code object (or name) -> [layer, name, events, ns, see-through]
        self._handlers: dict[Any, list] = {}
        self._sim = None
        self._net = None
        self._disks: tuple = ()
        self._counters0 = self._counters1 = (0, 0, 0, 0, 0)
        self._mark = self._marks()

    # -- attach -------------------------------------------------------------
    def start(self, sim, net=None, disks=()) -> "HostProfile":
        """Attach to `sim` and start the clock; `net` and `disks` are
        snapshotted for the message and force counts."""
        self._sim, self._net, self._disks = sim, net, tuple(disks)
        self._counters0 = self._counters()
        self._t0 = self._t = clock()
        self._mark = self._marks()
        sim.hostprof = self
        gc.callbacks.append(self._collection)
        return self

    def stop(self) -> None:
        """Detach and stop the clock: what remains goes to the layer on
        top, which is `outside` when called between simulator runs."""
        self._sim.hostprof = None
        gc.callbacks.remove(self._collection)
        self._t1 = clock()
        self.ns[self._top] += self._t1 - self._t
        self._t = self._t1
        self._counters1 = self._counters()

    def _counters(self) -> tuple[int, int, int, int, int]:
        net = self._net
        return (net.msgs_sent if net is not None else 0,
                net.bytes_sent if net is not None else 0,
                net.msgs_warm if net is not None else 0,
                net.delivery_rechecks if net is not None else 0,
                sum(d.forces for d in self._disks))

    # -- boundaries ---------------------------------------------------------
    def enter(self, layer: int) -> None:
        t = clock()
        self.ns[self._top] += t - self._t
        self._t = t
        self._stack.append(self._top)
        self._top = layer

    def leave(self) -> None:
        t = clock()
        self.ns[self._top] += t - self._t
        self._t = t
        self._top = self._stack.pop()

    def _collection(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.enter(GC)
        else:
            self.leave()
            self.gc_collections[info["generation"]] += 1

    def handler(self, fn: Callable) -> list:
        """[layer, name, events, ns, see-through] of a callable, made once
        per code object (functions and bound methods carry one; any other
        callable is keyed by its name).  See-through is set for the event
        core's callbacks that run one other callback (`CORE_CLOSURES`,
        `CORE_METHODS`): a function of the event's callback and arguments
        that returns the callback it runs."""
        code = getattr(fn, "__code__", None)
        key = code if code is not None else \
            getattr(fn, "__qualname__", type(fn).__name__)
        rec = self._handlers.get(key)
        if rec is None:
            if code is None:
                rec = [OTHER, key, 0, 0, None]
            else:
                name = code.co_qualname
                rec = [layer_of(code.co_filename, name), name, 0, 0,
                       _see_through(code)]
            self._handlers[key] = rec
        return rec

    def dispatch(self, fn: Callable, args: tuple) -> None:
        """Run one event's callback, from the loop's `sched`, under the
        callback's layer, and count the event under its handler.  An event
        core callback that runs one other callback (delivery, CPU
        completion) is seen through: its event is the other callback's."""
        try:
            rec = self._handlers[fn.__code__]
        except (AttributeError, KeyError):
            rec = self.handler(fn)
        if rec[4] is not None:
            rec = self.handler(rec[4](fn, args))
        ns = self.ns
        t_in = clock()
        ns[SCHED] += t_in - self._t
        self._top = rec[0]
        self._t = t_in
        fn(*args)
        t = clock()
        ns[self._top] += t - self._t
        self._t = t
        self._top = SCHED
        rec[2] += 1
        rec[3] += t - t_in

    def callback(self, fn: Callable, args: tuple) -> None:
        """Call `fn(*args)` under its own layer."""
        self.enter(self.handler(fn)[0])
        fn(*args)
        self.leave()

    def message(self, component: str) -> None:
        """Count a protocol message a node received, by component."""
        self.msgs[component] = self.msgs.get(component, 0) + 1

    # -- the device trace's clock --------------------------------------------
    def _events(self) -> int:
        return sum(r[2] for r in self._handlers.values())

    def _marks(self) -> tuple:
        return (list(self.ns), self._events(), self.pops,
                self.cancelled_pops)

    def batch_meta(self) -> dict:
        """Arguments of one sampler batch's trace annotation: each layer's
        self time (and `outside`) in µs since the previous batch, and the
        events, pops and cancelled pops since then (the simulator adds its
        pops up at the end of each `run`)."""
        t = clock()
        self.ns[self._top] += t - self._t
        self._t = t
        ns0, ev0, pops0, canc0 = self._mark
        self._mark = mark = self._marks()
        self.batches += 1
        meta = {f"{name}_us": (mark[0][i] - ns0[i]) / 1e3
                for i, name in enumerate(COLUMNS)}
        meta.update(events=mark[1] - ev0, pops=mark[2] - pops0,
                    cancelled_pops=mark[3] - canc0)
        return meta

    # -- result -------------------------------------------------------------
    def summary(self, top: int = 15) -> dict:
        """What was recorded between `start` and `stop`."""
        recs = [r for r in self._handlers.values() if r[2]]
        by_count = sorted(recs, key=lambda r: -r[2])[:top]
        by_ns = sorted(recs, key=lambda r: -r[3])[:top]
        m0, m1 = self._counters0, self._counters1
        return {
            "wall_ns": self._t1 - self._t0,
            "self_ns": dict(zip(COLUMNS, self.ns)),
            "pops": self.pops,
            "cancelled_pops": self.cancelled_pops,
            "heap_depth_sum": self.depth_sum,
            "heap_depth_max": self.depth_max,
            "events": self._events(),
            "handlers_by_count": [[r[1], r[2]] for r in by_count],
            "handlers_by_wall_ns": [[r[1], r[3]] for r in by_ns],
            "msgs_by_component": dict(sorted(self.msgs.items())),
            "msgs_sent": m1[0] - m0[0],
            "bytes_sent": m1[1] - m0[1],
            "msgs_warm": m1[2] - m0[2],
            "delivery_rechecks": m1[3] - m0[3],
            "disk_forces": m1[4] - m0[4],
            "sampler_batches": self.batches,
            "gc_collections": list(self.gc_collections),
        }
