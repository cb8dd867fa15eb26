"""Record the small profiler trace that `test_program_trace.py` reads.

    python3 bench/tests/record_program_trace.py <out.xplane.pb>

Run it on the chip.  It builds the section 9 deployment, preloads it,
starts its 32 closed-loop clients on an op stream that draws batches of
1,024 ops, and with the program's host profile attached runs 0.1 sim-s
in 5 ms slices inside the harness's window span.  It writes the trace's
`.xplane.pb` to the given path and prints the host profile's per-layer
self times and the idle gaps with their layer split.
"""

import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BATCH = 1024
SPAN_SIM_S = 0.1


def main(out: str) -> int:
    import jax
    from bench import harness, program_trace, trace_reduce
    from bench.cells import load_cell
    from repro.obs.hostprof import HostProfile
    from repro.workload.drivers import ClosedLoopDriver, SpinnakerAdapter
    from repro.workload.generators import OpStream

    cell = load_cell("s9-strong-closed32")
    spec = harness.workload_spec(cell)
    sim, cluster = harness.build_spinnaker(
        harness.experiment_config(cell, 1), num_keys=spec.num_keys)
    harness.preload(sim, cluster.make_client("preload"), spec.num_keys,
                    spec.value_size)
    stream = OpStream(spec, seed=harness.PLACEMENT_SEED, batch=BATCH)
    drv = ClosedLoopDriver(sim, SpinnakerAdapter(cluster.make_client("c")),
                           stream, harness.WindowLog(),
                           n_clients=cell.traffic["clients"])
    harness._start_without_running(drv, horizon=1e9, warmup=0.0)
    sim.run(until=sim.now + 0.05)             # compiles outside the trace
    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        hp = HostProfile().start(sim, cluster.net,
                                 [n.disk for n in cluster.nodes.values()])
        end = sim.now + SPAN_SIM_S
        while sim.now < end:
            sim.run(until=min(sim.now + harness.SLICE_SIM_S, end))
        hp.stop()
    jax.profiler.stop_trace()
    src = sorted(tmp.rglob("*.xplane.pb"))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    summary = hp.summary()
    print(json.dumps({"self_ns": summary["self_ns"],
                      "sampler_batches": summary["sampler_batches"],
                      "gaps": program_trace.read_file(Path(out))}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
