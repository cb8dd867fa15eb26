"""One run of one cell: set-up, the timed window, then the audits.

Set-up (timed as `setup_s`): the compile cache, the cluster as the
deployment states it, a preload of every key through the client's `put`,
and a warm-up of the traffic in which the sampler is compiled or loaded
from the cache.  The window: the program's closed- or open-loop driver
drives the client library over `Simulator.run` in short sim-time slices
until the wall clock reaches `seconds`.  After it, untimed: the clients
stop, the cluster settles, and the audits of `audit.py` decide `correct`.

The cluster runs with its tracer (`trace_sample`), profiler, journal and
watchdog off in every run.  A traced run adds only what the benchmark
observes with: a stack sampler, the JAX profiler and host spans.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import sys
import tempfile
import threading
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from . import audit, trace_reduce
from .cells import ROOT, Cell, metric_reader
from .sampler_ref import SamplerReference
from .stacks import StackSampler

import repro
from repro.compile_cache import use_compile_cache
from repro.core.cluster import key_of
from repro.workload.drivers import (ClosedLoopDriver, OpenLoopDriver,
                                    SpinnakerAdapter)
from repro.workload.experiment import ExperimentConfig, build_spinnaker
from repro.workload.generators import OpStream, WorkloadSpec
from repro.workload.scenario import parse_schedule

SLICE_SIM_S = 0.005          # sim-seconds per slice of the window
SETTLE_SIM_S = 3.0           # sim-seconds of idle cluster after the window
ANSWER_WAIT_SIM_S = 60.0     # longest wait for answers past the window
PRELOAD_DEADLINE_SIM_S = 120.0
HOST_SPANS = {"sim_slice", "sampler_refill"}
SAMPLER = "_sample_batch"
# Where the hot keys lie in the keyspace is part of the deployment, as in
# YCSB, whose scrambled zipfian hashes ranks with a fixed hash: every run
# scrambles with the offset of this seed, and the run's seed drives the
# draws.  A per-run placement would move the hot keys between ranges and
# change the work from seed to seed.
PLACEMENT_SEED = 0


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache in its fixed place, holding every
    program however quickly it compiled, so that only a checkout's first
    run compiles."""
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def seeds(seed: int) -> tuple[int, int]:
    """(simulator seed, sampler stream seed) from the run's seed."""
    sim_seed, stream_seed = np.random.SeedSequence(seed).generate_state(2)
    return int(sim_seed), int(stream_seed) & 0x7FFFFFFF


def workload_spec(cell: Cell) -> WorkloadSpec:
    cfg, mix = cell.config, cell.traffic["mix"]
    return WorkloadSpec(
        num_keys=cfg["num_keys"], key_dist=cfg["key_dist"],
        zipf_theta=cfg["zipf_theta"], scramble=cfg["scramble"],
        read_frac=mix["read"], write_frac=mix["write"],
        rmw_frac=mix["rmw"], cond_frac=mix["cond"],
        value_size=cfg["value_size"], value_size_dist="fixed")


def experiment_config(cell: Cell, sim_seed: int) -> ExperimentConfig:
    c = cell.config
    if c["replication"] != 3 or c["write_ack"] != "majority_forced":
        raise ValueError("the program builds 3-way cohorts that ack after "
                         "a majority has forced the write; the deployment "
                         "states otherwise")
    return ExperimentConfig(
        n_nodes=c["nodes"], disk=c["disk"], seed=sim_seed,
        commit_period=c["commit_period_s"], batch=c["batch"],
        batch_max_records=c["batch_max_records"],
        batch_deadline=c["batch_deadline_s"],
        ingress_batch=c["ingress_batch"], admission_limit=None,
        ranges_per_node=c["ranges_per_node"],
        lease_enabled=c["leases"], lease_duration=c["lease_s"],
        trace_sample=0.0, metrics_interval=0.0, profile=False,
        profile_interval=0.0, journal=False)


class RecordingStream(OpStream):
    """The program's op stream, keeping every batch it draws for the
    audit (the arrays `_refill` leaves behind are new each time)."""

    def __init__(self, spec: WorkloadSpec, seed: int,
                 annotate: bool = False):
        super().__init__(spec, seed=PLACEMENT_SEED)
        self._key = jax.random.PRNGKey(seed)
        self.batches: list[tuple] = []
        self._annotate = annotate

    def _refill(self) -> None:
        with (jax.profiler.TraceAnnotation("sampler_refill")
              if self._annotate else nullcontext()):
            super()._refill()
        self.batches.append((self._keys, self._ops, self._vsz, self._gaps))


class WindowLog:
    """The driver's log sink: every op the driver records, as columns."""

    def __init__(self):
        self.t, self.ok, self.lat = [], [], []

    def record(self, t: float, kind: str, ok: bool, latency: float) -> None:
        self.t.append(t)
        self.ok.append(ok)
        self.lat.append(latency)

    def between(self, t0: float, t1: float):
        t = np.asarray(self.t)
        sel = (t >= t0) & (t <= t1)
        return np.asarray(self.ok, bool)[sel], np.asarray(self.lat)[sel]


def _start_without_running(drv, horizon: float, warmup: float) -> None:
    """Start the driver's clients (or arrivals) with a far horizon but
    leave the clock to the caller, which runs it in slices."""
    sim = drv.sim
    sim.run = lambda until=None, max_events=None: None
    try:
        drv.run(horizon, warmup=warmup)
    finally:
        del sim.run


def preload(sim, client, n_keys: int, value_size: int) -> None:
    """Write every key once through the client's `put`, as version 1."""
    done = []
    value = b"x" * value_size
    for i in range(n_keys):
        client.put(key_of(i), "c", value,
                   lambda r: done.append(r.ok and r.version == 1))
    limit = sim.now + PRELOAD_DEADLINE_SIM_S
    while len(done) < n_keys and sim.now < limit:
        sim.run(until=sim.now + 0.25)
    if len(done) < n_keys or not all(done):
        raise RuntimeError(f"preload: {sum(done)} of {n_keys} keys written "
                           "at version 1")


@dataclasses.dataclass
class Faults:
    """Periodic fault schedule of an open-loop cell: the traffic's DSL
    lines, installed afresh at the start of every period, with `{rid}`
    rotating over the ranges."""
    period_s: float
    schedule: list
    rid_step: int
    kills: list = dataclasses.field(default_factory=list)
    # one entry per crash: (period, sim time, node, ranges it led)

    def install(self, sim, cluster, k: int, t_start: float) -> None:
        n_ranges = len(cluster.ranges)
        text = "\n".join(self.schedule).format(rid=(k * self.rid_step)
                                               % n_ranges)
        sched = parse_schedule(text)
        for ev in sched.events:
            if ev.action.startswith("crash"):
                # fires before the crash at the same instant: who led what
                sim.at(t_start + ev.t, self._before_crash, sim, cluster,
                       sched, k)
        sched.install(sim, cluster, at=t_start)

    def _before_crash(self, sim, cluster, sched, k) -> None:
        leaders = {}
        for rid in cluster.ranges:
            rep = cluster.leader_replica(rid)
            if rep is not None:
                leaders[rid] = rep.node.node_id
        n_before = len(sched.applied_events)

        def after():
            for ev in sched.applied_events[n_before:]:
                if ev.action == "crash":
                    led = sorted(r for r, n in leaders.items()
                                 if n == ev.node)
                    self.kills.append((k, sim.now, ev.node, led))
        sim.schedule(0.0, after)


@dataclasses.dataclass
class Observed:
    """What per-layer metric readers read (`bench/metrics/*.py`)."""
    window_s: float
    ops_ok: int
    events: int
    layer_samples: dict
    trace: Optional[trace_reduce.TraceSummary]
    device_kind: str
    sampler_batch: int
    cluster_events: list
    kills: list


def unavailable_s(kills: list, history: audit.History, cluster,
                  t_limit: float) -> Optional[float]:
    """Mean over the ranges each kill took the leader of: sim-seconds from
    the kill to the first write acknowledged on that range (to `t_limit`
    where none was)."""
    acks: dict[int, list] = {}
    for key, _t0, t1, ok, *_ in history.writes:
        if ok:
            acks.setdefault(cluster.range_of(key), []).append(t1)
    for v in acks.values():
        v.sort()
    gaps = []
    for _k, t_kill, _node, rids in kills:
        for rid in rids:
            later = [t for t in acks.get(rid, ()) if t > t_kill]
            gaps.append(min(later[0] if later else t_limit, t_limit) - t_kill)
    return float(np.mean(gaps)) if gaps else None


class Observer:
    """What a traced run adds: the JAX profiler, the stack sampler and the
    harness's host spans.  Inert in an untraced run."""

    def __init__(self, on: bool):
        self.on = on
        self.sampler = self._dir = None

    def span(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.on \
            else nullcontext()

    def start(self) -> None:
        if not self.on:
            return
        self._dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self._dir), profiler_options=opts)
        self.sampler = StackSampler(Path(list(repro.__path__)[0]),
                                    threading.get_ident())
        # let the sampler thread take the interpreter lock every ~1 ms
        self._switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-3)
        self.sampler.start()

    def stop(self) -> Optional[trace_reduce.TraceSummary]:
        if not self.on:
            return None
        self.sampler.stop()
        sys.setswitchinterval(self._switch)
        jax.profiler.stop_trace()
        try:
            traces = sorted(self._dir.rglob("*.xplane.pb"))
            return trace_reduce.reduce_file(traces[-1], SAMPLER, HOST_SPANS) \
                if traces else None
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def _settle(sim, cluster, history: audit.History, faults) -> None:
    """Let pending faults fire, restart crashed nodes, settle the cluster
    and wait for every request the window issued to be answered."""
    sim.run(until=sim.now + (faults.period_s if faults else 0.0))
    for nid, node in sorted(cluster.nodes.items()):
        if not node.up:
            cluster.restart_node(nid)
    sim.run(until=sim.now + SETTLE_SIM_S)
    cluster.settle(timeout=30.0)
    limit = sim.now + ANSWER_WAIT_SIM_S
    while history.pending and sim.now < limit:
        sim.run(until=sim.now + 0.25)
    sim.run(until=sim.now + SETTLE_SIM_S)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_setup0: float, control: Optional[str] = None,
             devices: Optional[list] = None) -> dict:
    """One run; returns the result line's object.  `control` plants the
    benchmark's control in the program's place: `timeline_reads` serves
    the strong reads the traffic asks for from any replica, and
    `sampler_bf16` draws the ops from the reference in bfloat16."""
    t_enter = time.perf_counter()
    enable_compile_cache()
    devices = devices or jax.devices()
    dev = devices[0]
    sim_seed, stream_seed = seeds(seed)
    traffic = cell.traffic
    if traffic["read"] != "strong":
        raise ValueError("only strong reads are audited")
    spec = workload_spec(cell)

    # -- set-up ------------------------------------------------------------
    sim, cluster = build_spinnaker(experiment_config(cell, sim_seed),
                                   num_keys=spec.num_keys)
    n_ranges = cell.config["nodes"] * cell.config["ranges_per_node"]
    if len(cluster.ranges) != n_ranges or any(
            len(m) != 3 for m in cluster.members.values()):
        raise RuntimeError("cluster layout differs from the deployment")
    t_built = time.perf_counter()
    preload(sim, cluster.make_client("preload"), spec.num_keys,
             spec.value_size)
    t_loaded = time.perf_counter()
    history = audit.History(sim, strong_reads=True)
    client = cluster.make_client("bench")
    history.attach(client)
    adapter = SpinnakerAdapter(client, consistent=control != "timeline_reads")
    stream = RecordingStream(spec, seed=stream_seed, annotate=trace)
    ref = SamplerReference(stream_seed, PLACEMENT_SEED, spec.num_keys,
                           spec.zipf_theta, list(spec.mix()),
                           spec.value_size, stream.batch)
    if control == "sampler_bf16":
        _plant_bf16_sampler(stream, stream_seed, spec)
    log = WindowLog()
    if traffic["driver"] == "closed":
        drv = ClosedLoopDriver(sim, adapter, stream, log,
                               n_clients=traffic["clients"])
    else:
        drv = OpenLoopDriver(sim, adapter, stream, log, rate=traffic["rate"])
    faults = Faults(**{k: traffic["faults"][k] for k in
                       ("period_s", "schedule", "rid_step")}) \
        if "faults" in traffic else None
    warmup = traffic["warmup_sim_s"]
    _start_without_running(drv, horizon=1e9, warmup=warmup)
    observer = Observer(trace)
    with jax.default_device(dev):
        sim.run(until=sim.now + warmup)

        # -- the timed window ----------------------------------------------
        t_w0, ev0 = sim.now, sim.events_processed
        observer.start()
        w0 = time.perf_counter()
        period_walls = []           # wall at the end of each whole period
        next_b = t_w0 + (faults.period_s if faults else math.inf)
        with observer.span(trace_reduce.WINDOW):
            if faults:
                faults.install(sim, cluster, 0, t_w0)
            while time.perf_counter() - w0 < seconds:
                with observer.span("sim_slice"):
                    sim.run(until=min(sim.now + SLICE_SIM_S, next_b))
                if sim.now >= next_b:
                    period_walls.append(time.perf_counter() - w0)
                    faults.install(sim, cluster, len(period_walls), next_b)
                    next_b += faults.period_s
        w1 = time.perf_counter() - w0
        t_w1, ev1 = sim.now, sim.events_processed
        summary = observer.stop()
    drv._t_end = sim.now            # the clients stop issuing

    if faults:
        need = traffic["faults"]["min_periods"]
        if len(period_walls) < need:
            raise RuntimeError(
                f"{len(period_walls)} whole fault periods in {w1:.1f} s of "
                f"wall (they ended at {period_walls} s; {t_w1 - t_w0:.3f} "
                f"sim-s in all); the cell needs {need}")
        window_s = period_walls[-1]
        t_end = t_w0 + len(period_walls) * faults.period_s
        kills = [x for x in faults.kills if x[0] < len(period_walls)]
    else:
        window_s, t_end, kills = w1, t_w1, []
    ok, lat = log.between(t_w0, t_end)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])

    # -- after the window, untimed -------------------------------------------
    _settle(sim, cluster, history, faults)
    checks = audit.run_checks(stream.batches, ref, cluster, history)

    out = {"correct": checks.correct, "attempted": int(ok.size),
           "failed": int(ok.size - ok.sum())}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if not trace:
        values = {"sim_ops_per_s": ok.sum() / window_s,
                  "op_p99_sim_ms": np.percentile(lat, 99) * 1e3
                  if lat.size else None,
                  "setup_s": w0 - t_setup0,
                  "unavailable_sim_s": unavailable_s(kills, history,
                                                     cluster, t_end)
                  if faults else None}
        metrics = {}
        for m in cell.end_to_end:
            if values[m["name"]] is None:
                raise RuntimeError(f"no reading for {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        samples = dict(observer.sampler.counts)
        obs = Observed(window_s=w1,
                       ops_ok=int(log.between(t_w0, t_w1)[0].sum()),
                       events=ev1 - ev0, layer_samples=samples,
                       trace=summary, device_kind=dev.device_kind,
                       sampler_batch=stream.batch,
                       cluster_events=list(cluster.obs.events.events),
                       kills=kills)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"], ROOT)(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            out["breakdown"] = {"device_ops": summary.device_ops,
                                "idle_gaps": summary.idle_gaps}
        out["host_samples"] = {k or "outside_program": v
                               for k, v in samples.items()}
    out["metrics"] = metrics
    out["device"] = device
    out["window"] = {"wall_s": w1, "sim_s": t_w1 - t_w0,
                     "periods": len(period_walls),
                     "batches": len(stream.batches)}
    out["setup_split_s"] = {"start_and_jax": t_enter - t_setup0,
                            "build": t_built - t_enter,
                            "preload": t_loaded - t_built,
                            "warmup": w0 - t_loaded}
    out["checks"] = checks.table()
    out["_detail"] = checks.detail
    return out


def _plant_bf16_sampler(stream: RecordingStream, stream_seed: int,
                        spec: WorkloadSpec) -> None:
    """The control: the reference draw in bfloat16 in the sampler's place."""
    import ml_dtypes
    low = SamplerReference(stream_seed, PLACEMENT_SEED, spec.num_keys,
                           spec.zipf_theta, list(spec.mix()),
                           spec.value_size, stream.batch,
                           dtype=ml_dtypes.bfloat16)
    n = [0]

    def refill():
        stream._keys, stream._ops, stream._vsz, gaps = low.batch(n[0])
        stream._gaps = gaps.astype(np.float32)
        n[0] += 1
        stream._i = 0
        stream.sampled += stream.batch
        stream.batches.append((stream._keys, stream._ops, stream._vsz,
                               stream._gaps))
    stream._refill = refill
