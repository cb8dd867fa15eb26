"""Find the modeled knee of an open-loop cell: sweep offered rates.

    python3 bench/sweep.py --workload s9-leaderkill-open --rates 20000,40000 \
        [--sim-seconds 1.0] [--seed 1]

For each offered rate it builds the cell's cluster afresh, drives the
cell's op mix open-loop without faults for `--sim-seconds` of simulated
time after the warm-up, and prints one JSON line: the rate completed, the
modeled p50/p99 and the requests still outstanding at the end (a backlog
that grows with the rate is past the knee).  The cell's traffic file then
states the rate chosen from these lines; the benchmark never searches.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def sweep_point(cell, rate: float, sim_seconds: float, seed: int) -> dict:
    import numpy as np
    from bench import harness
    from repro.workload.drivers import OpenLoopDriver, SpinnakerAdapter

    sim_seed, stream_seed = harness.seeds(seed)
    spec = harness.workload_spec(cell)
    sim, cluster = harness.build_spinnaker(
        harness.experiment_config(cell, sim_seed), num_keys=spec.num_keys)
    harness.preload(sim, cluster.make_client("preload"), spec.num_keys,
                    spec.value_size)
    log = harness.WindowLog()
    stream = harness.RecordingStream(spec, seed=stream_seed)
    drv = OpenLoopDriver(sim, SpinnakerAdapter(cluster.make_client("bench")),
                         stream, log, rate=rate)
    warm = cell.traffic["warmup_sim_s"]
    t0 = time.perf_counter()
    drv.run(sim_seconds, warmup=warm)
    wall = time.perf_counter() - t0
    ok, lat = log.between(0.0, sim.now)
    return {"offered": rate, "completed_per_sim_s": float(ok.sum())
            / sim_seconds, "failed": int(ok.size - ok.sum()),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "outstanding_at_end": drv.outstanding, "wall_s": wall}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--sim-seconds", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from bench.cells import load_cell
    cell = load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        print(json.dumps(sweep_point(cell, rate, args.sim_seconds,
                                     args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
