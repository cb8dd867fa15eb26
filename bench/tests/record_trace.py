"""Record the small profiler trace that `test_bench_trace.py` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Run it on the chip.  It opens the harness's window span, draws four
sampler batches of the section 9 deployment's stream inside
`sampler_refill` spans, each after a `sim_slice` span in which the host
only sleeps, and writes the trace's `.xplane.pb` to the given path.  It
prints the trace's planes and lines, with the first events of each.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

N_BATCHES = 4


def main(out: str) -> int:
    import jax
    from bench import trace_reduce
    from bench.harness import RecordingStream, workload_spec
    from bench.cells import load_cell

    spec = workload_spec(load_cell("s9-strong-closed32"))
    stream = RecordingStream(spec, seed=1, annotate=True)
    stream._refill()                          # compile outside the trace
    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(N_BATCHES):
            with jax.profiler.TraceAnnotation("sim_slice"):
                time.sleep(0.02)
            stream._refill()
    jax.profiler.stop_trace()
    src = sorted(tmp.rglob("*.xplane.pb"))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:4]])
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
