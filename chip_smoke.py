"""Bring-up smoke: the paper-§9 deployment, end to end, on one TPU chip.

    python chip_smoke.py

One process owns the chip and starts no JAX child.  It runs what
`benchmarks/spinnaker_bench.py` runs, through the same builders, and
writes nothing in the repo outside the compile cache.  Phases:

1. device    — the first device must be a TPU; there is no CPU fallback;
2. generator — draws `N_BATCHES` sampler batches from two 1M-key
               `OpStream`s on the chip and again on the host CPU: keys,
               op kinds and value sizes must be bit-identical (the gaps
               may round differently; their largest ulp gap is printed),
               plus the per-batch device time of the sampler;
3. steady    — the fig8 Spinnaker strong-read arm at the bench's full
               settings with only the measured window cut, then the same
               run with the sampler pinned to the CPU as the plain
               reference: the result blocks must be identical;
4. failover  — the fig9 leader kill on the same deployment, shortened:
               writes must resume before the killed node comes back.

Wall times and ops per wall-second printed on the way are bring-up
observations, not benchmark metrics.  Any failed check raises; the last
line of stdout, printed only when every phase passed, is one JSON object
naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks"))

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.workload import (OpStream, WorkloadSpec,  # noqa: E402
                            generators, run_spinnaker_workload)
from spinnaker_bench import (LEADER_KILL, base_cfg, base_spec,  # noqa: E402
                             check_writes_resume)

N_BATCHES = 64              # sampler batches drawn per generator stream
BATCH = 8192                # OpStream's batch
GEN_KEYS = 1_000_000        # largest keyspace whose float32 CDF is exact
STEADY_WINDOW_S = 2.5       # measured sim-time window (bench: 15 s)
FAILOVER_S, T_KILL, T_BACK = 5.0, 1.5, 3.75   # bench: 30 s, 8 s, 22.5 s


class SamplerProbe:
    """Counts sampler batches and the devices their outputs live on, by
    wrapping the module attribute that `OpStream._refill` calls."""

    def __init__(self) -> None:
        self.batches = 0
        self.devices: set = set()

    def __enter__(self) -> "SamplerProbe":
        self._inner = generators._sample_batch

        def counted(*args, **kwargs):
            out = self._inner(*args, **kwargs)
            self.batches += 1
            for x in out:
                self.devices |= x.devices()
            return out

        generators._sample_batch = counted
        return self

    def __exit__(self, *exc) -> None:
        generators._sample_batch = self._inner

    def expect(self, device) -> None:
        if self.devices != {device}:
            raise AssertionError(f"sampler outputs on {self.devices}, "
                                 f"expected {device}")


def _draw(spec: WorkloadSpec, device, seed: int = 0):
    """`N_BATCHES` batches of one stream through the public iterator:
    (key indexes, op kinds, value sizes, unit-rate gaps)."""
    n = N_BATCHES * BATCH
    keys = np.empty(n, np.int64)
    kinds = np.empty(n, np.int64)
    vsz = np.empty(n, np.int64)
    gaps = np.empty(n, np.float32)
    with jax.default_device(device), SamplerProbe() as probe:
        s = OpStream(spec, seed=seed, batch=BATCH)
        for i in range(n):
            gaps[i] = s.next_gap(1.0)
            op = s.next_op()
            keys[i], kinds[i], vsz[i] = op.key_index, op.kind, op.value_size
    probe.expect(device)
    return (keys, kinds, vsz, gaps), probe.batches


def _ulps(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Distance in float32 ulps per element (gaps are >= 0, so the int32
    views order like the floats)."""
    return np.abs(a.view(np.int32).astype(np.int64)
                  - b.view(np.int32).astype(np.int64))


def _sampler_batch_ms(spec: WorkloadSpec, device, reps: int = 50):
    """Per-batch device time of the jitted sampler: the first call (it
    compiles only if no draw did before) and the median and min of `reps`
    warm calls, each ended by
    `block_until_ready`.  The arguments are the ones `OpStream._refill`
    passes."""
    with jax.default_device(device):
        s = OpStream(spec, batch=BATCH)
        keys = jax.random.split(jax.random.PRNGKey(1), reps + 1)

        def call(k):
            return jax.block_until_ready(generators._sample_batch(
                k, s._cdf, s._mix_cdf, spec.num_keys, spec.value_size,
                s._vmin, s._vmax, BATCH))

        t0 = time.perf_counter()
        call(keys[0])
        first_ms = (time.perf_counter() - t0) * 1e3
        times = []
        for k in keys[1:]:
            t0 = time.perf_counter()
            call(k)
            times.append((time.perf_counter() - t0) * 1e3)
    return first_ms, float(np.median(times)), float(np.min(times))


def phase_generator(accel, cpu) -> dict:
    zipf = dataclasses.replace(base_spec(False), num_keys=GEN_KEYS)
    uniform = dataclasses.replace(zipf, key_dist="uniform",
                                  value_size_dist="uniform")
    out = {"batches": 0, "wall_s": 0.0}
    for name, spec in (("zipfian", zipf), ("uniform", uniform)):
        t0 = time.perf_counter()
        got, nb = _draw(spec, accel)
        wall = time.perf_counter() - t0
        ref, _ = _draw(spec, cpu)
        for what, a, b in zip(("keys", "op kinds", "value sizes"),
                              got[:3], ref[:3]):
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"{name}: {what} differ from the CPU draw at "
                    f"{int(np.count_nonzero(a != b))} of {a.size} ops")
        ulps = _ulps(got[3], ref[3])
        worst = int(np.argmax(ulps))
        ulp = int(ulps[worst])
        print(f"generator {name}: {nb} batches x {BATCH} ops, keys/kinds/"
              f"sizes bit-identical to CPU; gaps: "
              f"{int(np.count_nonzero(ulps))} of {ulps.size} differ, ulp "
              f"p50 {np.percentile(ulps, 50):.0f} p99 "
              f"{np.percentile(ulps, 99):.0f} max {ulp} (CPU gap "
              f"{float(ref[3][worst]):.7g}); draw {wall:.3f} s wall",
              flush=True)
        out["batches"] += nb
        out["wall_s"] += wall
        out[f"{name}_gap_max_ulp"] = ulp
    for name, spec in (("zipfian", zipf), ("uniform", uniform)):
        first, med, low = _sampler_batch_ms(spec, accel)
        print(f"generator sampler batch, {name} {GEN_KEYS} keys, {BATCH} "
              f"ops: first call {first:.3f} ms, warm median {med:.4f} ms, "
              f"min {low:.4f} ms", flush=True)
        out[f"{name}_batch_ms"] = med
    return out


def _run(device, spec, cfg, **kw) -> tuple[dict, float, SamplerProbe]:
    t0 = time.perf_counter()
    with jax.default_device(device), SamplerProbe() as probe:
        r = run_spinnaker_workload(spec, cfg, **kw)
    wall = time.perf_counter() - t0
    probe.expect(device)
    if not r["trace_audit"]["ok"]:
        raise AssertionError(f"trace audit: {r['trace_audit']}")
    if not (r["reads"]["count"] and r["writes"]["count"]):
        raise AssertionError(f"no reads or no writes completed: "
                             f"{r['reads']['count']} / "
                             f"{r['writes']['count']}")
    return r, wall, probe


def _observed(name: str, r: dict, wall: float, probe: SamplerProbe) -> None:
    print(f"{name}: {r['total_ops']} ops in the measured window, "
          f"{wall:.3f} s wall, {r['total_ops'] / wall:.1f} ops/wall-s, "
          f"{probe.batches} sampler batches; reads p50 "
          f"{r['reads']['p50_ms']:.4f} ms p99 {r['reads']['p99_ms']:.4f} ms, "
          f"writes p50 {r['writes']['p50_ms']:.4f} ms "
          f"p99 {r['writes']['p99_ms']:.4f} ms, "
          f"throughput {r['throughput']:.1f}/s", flush=True)


def _result_block(r: dict) -> dict:
    return {k: r[k] for k in ("total_ops", "throughput", "reads", "writes",
                              "driver")}


def phase_steady(accel, cpu) -> dict:
    spec = base_spec(False)
    cfg = base_cfg(False)
    print(f"steady: fig8 spinnaker strong reads, {cfg.n_nodes} nodes "
          f"{cfg.disk}, {cfg.ranges_per_node} ranges/node, "
          f"{cfg.n_clients} clients, {spec.num_keys} keys; measured window "
          f"cut from {cfg.duration} s to {STEADY_WINDOW_S} s of sim time "
          f"(warmup {cfg.warmup} s kept)", flush=True)
    cfg = dataclasses.replace(cfg, duration=STEADY_WINDOW_S)
    r, wall, probe = _run(accel, spec, cfg, consistent_reads=True)
    _observed("steady (sampler on chip)", r, wall, probe)
    ref, ref_wall, ref_probe = _run(cpu, spec, cfg, consistent_reads=True)
    _observed("steady (sampler on CPU, reference)", ref, ref_wall, ref_probe)
    if _result_block(r) != _result_block(ref):
        raise AssertionError(
            f"result block differs from the CPU-sampler reference:\n"
            f"  chip: {_result_block(r)}\n  cpu:  {_result_block(ref)}")
    print("steady: result block identical to the CPU-sampler reference",
          flush=True)
    return {"wall_s": wall, "ops": r["total_ops"], "batches": probe.batches}


def phase_failover(accel) -> dict:
    spec = base_spec(False)
    cfg = dataclasses.replace(base_cfg(False, seed=1), duration=FAILOVER_S,
                              window=0.5)
    sched = LEADER_KILL.format(t_kill=T_KILL, t_back=T_BACK)
    print(f"failover: fig9 leader kill, {FAILOVER_S} s measured (bench 30 s)"
          f", kill at {T_KILL} s, restart at {T_BACK} s", flush=True)
    r, wall, probe = _run(accel, spec, cfg, consistent_reads=True,
                          schedule=sched)
    _observed("failover", r, wall, probe)
    check = check_writes_resume({**r, "t_kill": T_KILL})
    rec = check["recovery_window_start_s_after_kill"]
    if not check["writes_resumed"] or T_KILL + rec >= T_BACK:
        raise AssertionError(f"writes did not resume before the restart: "
                             f"{check}")
    print(f"failover: writes resumed in the window starting {rec} s after "
          f"the kill ({r['fault_events']})", flush=True)
    return {"wall_s": wall, "ops": r["total_ops"], "batches": probe.batches}


def _cache_entries(path: Path) -> int:
    return sum(1 for p in path.rglob("*") if p.is_file()) \
        if path.is_dir() else 0


def main() -> int:
    cache = use_compile_cache()
    before = _cache_entries(cache)
    devices = jax.devices()
    accel = devices[0]
    print(f"device: platform={accel.platform} kind={accel.device_kind} "
          f"count={len(devices)}", flush=True)
    if accel.platform != "tpu":
        print("chip_smoke: no TPU found; there is no CPU fallback",
              file=sys.stderr)
        return 1
    cpu = jax.devices("cpu")[0]

    walls = {}
    t0 = time.perf_counter()
    gen = phase_generator(accel, cpu)
    walls["generator"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    steady = phase_steady(accel, cpu)
    walls["steady"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    fail = phase_failover(accel)
    walls["failover"] = time.perf_counter() - t0

    print("bring-up observations (not benchmark metrics):", flush=True)
    print(f"  generator: {walls['generator']:.3f} s wall incl. CPU draws; "
          f"{gen['batches']} batches on the chip; sampler batch "
          f"zipfian {gen['zipfian_batch_ms']:.4f} ms uniform "
          f"{gen['uniform_batch_ms']:.4f} ms; gap max ulp zipfian "
          f"{gen['zipfian_gap_max_ulp']} uniform "
          f"{gen['uniform_gap_max_ulp']}", flush=True)
    for name, p in (("steady", steady), ("failover", fail)):
        print(f"  {name}: {walls[name]:.3f} s phase wall; chip run "
              f"{p['wall_s']:.3f} s, {p['ops'] / p['wall_s']:.1f} simulated "
              f"ops per wall-second, {p['batches']} sampler batches",
              flush=True)
    after = _cache_entries(cache)
    print(f"compile cache {cache}: {before} -> {after} entries "
          f"({'gained' if after > before else 'no new'} entries)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": accel.platform, "kind": accel.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
