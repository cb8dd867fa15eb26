"""Readings for the limits of `audit.LIMITS`, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --controls none,timeline_reads,sampler_bf16 --seconds <s>

For every seed and every control it runs the cell once, as `run.py` does
(`none` is the program as it is), and prints one JSON line: the seed, the
control, `correct` and each number compared with its limit.  The lower
reading of a limit is the largest that sound runs give; the upper one the
smallest that a control gives.  The benchmark's own runs never plant a
control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROLS = ("none", "timeline_reads", "sampler_bf16")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import jax
    from bench.cells import load_cell
    from bench.harness import run_cell
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    cell = load_cell(args.workload)
    for control in args.controls.split(","):
        if control not in CONTROLS:
            raise SystemExit(f"unknown control {control!r}")
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run_cell(cell, seed, args.seconds, False,
                           time.perf_counter(),
                           control=None if control == "none" else control)
            print(json.dumps({"cell": cell.name, "seed": seed,
                              "control": control,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
