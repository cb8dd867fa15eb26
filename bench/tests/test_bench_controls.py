"""The rest of a run, without the look for a chip, on the CPU: a sound run
comes out correct, and the controls come out not correct.

Each run is the cell as `BENCHMARK.json` states it with a window of about
a second of wall (a few thousand ops)."""

import time

from bench import cells, harness

SECONDS = 1.0


def run(name, **kw):
    return harness.run_cell(cells.load_cell(name), seed=2**31 + 7,
                            seconds=SECONDS, trace=False,
                            t_setup0=time.perf_counter(), **kw)


def test_sound_run_is_correct():
    out = run("ycsb-a-closed32")
    assert out["correct"], out["_detail"]
    assert out["attempted"] > 100 and out["failed"] == 0
    assert set(out["metrics"]) == {"sim_ops_per_s", "op_p99_sim_ms",
                                   "setup_s"}
    assert list(out)[-2:] == ["checks", "_detail"]
    assert all(v <= lim for v, lim in out["checks"].values())


def test_control_timeline_reads_fails():
    """Strong reads served by any replica: stale reads."""
    out = run("s9-strong-closed32", control="timeline_reads")
    assert not out["correct"]
    assert out["checks"]["lin_violations"][0] > 0
    assert {v["rule"] for v in out["_detail"]["violations"]} == {"R1"}


def test_control_bf16_sampler_fails():
    out = run("ycsb-b-closed32", control="sampler_bf16")
    assert not out["correct"]
    mism, lim = out["checks"]["sampler_mismatches"]
    assert mism > 1000 and lim == 0
    gap, lim = out["checks"]["gap_rel_err"]
    assert gap > 3 * lim
