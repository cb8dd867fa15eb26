"""The reading of the program's host profile: per-layer numbers from its
summary, and the idle gaps split by the program's per-batch annotations,
on synthetic planes and on a small trace recorded on a TPU v5e chip by
`record_program_trace.py`."""

from pathlib import Path

import pytest

from bench import program_trace, trace_reduce

TRACE = Path(__file__).parent / "data" / "program_v5e.xplane.pb"


def _summary(**kw):
    s = {"self_ns": {"sched": 4_000_000, "net": 1_000_000,
                     "queues": 500_000, "node": 800_000,
                     "protocol": 2_000_000, "client": 900_000,
                     "workload": 700_000, "sampler_wait": 3_000_000,
                     "gc": 600_000, "other": 0, "outside": 100_000},
         "sampler_batches": 2, "pops": 1000, "cancelled_pops": 150,
         "heap_depth_sum": 2_000_000, "msgs_sent": 250}
    s.update(kw)
    return s


def test_host_metrics():
    m = program_trace.host_metrics(_summary(), ops_ok=100)
    assert m["span_us_per_op.sched"] == pytest.approx(40.0)
    assert m["span_us_per_op.workload"] == pytest.approx(7.0)
    assert m["span_us_per_op.gc"] == pytest.approx(6.0)
    assert m["sampler_host_wait_us_per_batch"] == pytest.approx(1500.0)
    assert m["cancelled_pop_pct"] == pytest.approx(15.0)
    assert m["heap_depth_mean"] == pytest.approx(2000.0)
    assert m["msgs_per_op"] == pytest.approx(2.5)
    assert len(m) == 12


def test_host_metrics_find_nothing_without_a_profile():
    assert program_trace.host_metrics(None, 100) == {}
    assert program_trace.host_metrics(_summary(), 0) == {}
    m = program_trace.host_metrics(_summary(sampler_batches=0, pops=0), 10)
    assert "sampler_host_wait_us_per_batch" not in m
    assert "heap_depth_mean" not in m and "cancelled_pop_pct" not in m


class _E:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _meta(**layers):
    return [(f"{k}_us", v) for k, v in layers.items()] + [("events", 7)]


MS = 1_000_000      # ns


def test_gaps_split_by_the_annotation_that_follows():
    host = _P("/host:CPU", [_L("python3", [
        _E(trace_reduce.WINDOW, 0, 100 * MS),
        _E(program_trace.BATCH_SPAN, 30 * MS, 4 * MS,
           _meta(sched=15.0, client=14.0, sampler_wait=9.0, outside=0.0)),
        _E(program_trace.BATCH_SPAN, 80 * MS, 5 * MS,
           _meta(sched=30.0, protocol=15.0, sampler_wait=3.0))])])
    # the second batch's programs start a little before its annotation
    dev = _P("/device:TPU:0", [_L("XLA Ops", [
        _E("%a = x", 30 * MS, 2 * MS), _E("%s = x", 79 * MS, MS // 10),
        _E("%b = x", 79.5 * MS, 2 * MS)])])
    gaps = program_trace.idle_gap_layers([host, dev])
    assert [round(g["gap_s"] * 1e3, 1) for g in gaps] == [
        47.0, 30.0, 18.5, 0.4]
    # 32-79 ms: the second annotation is the first to open after it; its
    # layers, without the previous batch's sampler wait
    assert gaps[0]["layers_us"] == {"sched": 30.0, "protocol": 15.0}
    assert gaps[1]["layers_us"] == {"sched": 15.0, "client": 14.0,
                                    "outside": 0.0}
    assert gaps[2]["layers_us"] is None       # the window's end
    assert gaps[3]["layers_us"] is None       # inside one batch
    assert program_trace.idle_gap_layers([host, dev], top=1) == gaps[:1]


def test_gaps_need_the_window():
    with pytest.raises(ValueError):
        program_trace.idle_gap_layers([_P("/host:CPU", [])])


@pytest.fixture(scope="module")
def chip_gaps():
    return program_trace.read_file(TRACE)


def test_chip_trace_gaps_are_split_by_layer(chip_gaps):
    split = [g for g in chip_gaps if g["layers_us"] is not None]
    assert len(split) >= 3
    for g in split:
        assert sum(g["layers_us"].values()) * 1e-6 == pytest.approx(
            g["gap_s"], rel=0.05)
        assert g["layers_us"]["sched"] > 0
        assert g["layers_us"]["protocol"] > 0
    # the rest: the window's end, and the gaps between one batch's programs
    assert all(g["gap_s"] * 1e9 < program_trace.CLOCK_SKEW_NS
               for g in chip_gaps[len(split) + 1:])
