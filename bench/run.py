"""Run one cell of the benchmark once, on the machine's accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, and last `checks`: each number compared, with its limit.
The same numbers end standard error.  With no TPU, or fewer chips than
the cell asks for, it exits 1 and prints no result.
"""

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.cells import load_cell
    cell = load_cell(args.workload)
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: the cell needs {cell.chips} TPU chip(s); there is no "
              "CPU fallback", file=sys.stderr)
        return 1

    from bench.harness import run_cell
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   T_START, devices=devices)
    detail = out.pop("_detail")
    if not out["correct"]:
        print(f"detail: {json.dumps(detail, default=str)}", file=sys.stderr)
    for name, (value, limit) in out["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
