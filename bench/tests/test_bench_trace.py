"""The trace reduction, on a small profiler trace recorded on a TPU v5e
chip by `record_trace.py`: four sampler batches, each after 20 ms of host
sleep, inside the harness's window span."""

from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "sampler_v5e.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_file(TRACE, "_sample_batch",
                                    {"sim_slice", "sampler_refill"})


def test_window_and_device(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(0.103756843)
    # the sampler is the only work on the device inside the window
    assert summary.busy_s == pytest.approx(0.003108229)
    assert 0 < summary.busy_s < summary.window_s


def test_sampler_runs(summary):
    assert summary.sampler_runs == 4
    assert summary.sampler_s == pytest.approx(0.003108552)
    per_batch_us = summary.sampler_s / summary.sampler_runs * 1e6
    assert 700 < per_batch_us < 850


def test_device_ops_named_by_module(summary):
    names = [n for n, _s in summary.device_ops]
    assert all(n.startswith("jit__sample_batch/") for n in names)
    assert names[0] == "jit__sample_batch/while.7"


def test_idle_gaps_named_by_host_span(summary):
    gaps = summary.idle_gaps
    assert len(gaps) == 10
    assert [n for n, _ in gaps[:4]] == ["sim_slice"] * 4
    assert all(0.02 < s < 0.025 for _, s in gaps[:4])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_union_and_gaps():
    busy = trace_reduce.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert trace_reduce.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert trace_reduce.gaps(busy, 0, 9) == [(3, 5)]
    assert trace_reduce.gaps([], 0, 4) == [(0, 4)]


class _E:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_reduce_clips_to_window_and_averages_devices():
    host = _P("/host:CPU", [_L("python3", [
        _E(trace_reduce.WINDOW, 100, 1000), _E("sim_slice", 100, 600),
        _E("sampler_refill", 700, 400)])])
    dev0 = _P("/device:TPU:0", [
        _L("XLA Modules", [_E("jit__sample_batch(1)", 750, 100),
                           _E("jit_other(2)", 0, 150)]),
        _L("XLA Ops", [_E("%a = f32[] add()", 750, 100),
                       _E("%b = f32[] mul()", 0, 150)])])
    dev1 = _P("/device:TPU:1", [_L("XLA Ops", [_E("%c = x", 200, 300)])])
    s = trace_reduce.reduce_planes([host, dev0, dev1], "_sample_batch",
                                   {"sim_slice", "sampler_refill"})
    assert s.window_s == pytest.approx(1e-6)
    # device 0: 50 ns of `b` inside the window plus 100 of `a`; device 1: 300
    assert s.busy_s == pytest.approx((150 + 300) / 2 * 1e-9)
    assert s.sampler_runs == 1 and s.sampler_s == pytest.approx(100e-9)
    assert dict(map(tuple, s.device_ops)) == pytest.approx(
        {"jit_other/b": 50e-9, "jit__sample_batch/a": 100e-9,
         "?/c": 300e-9})
    # free: 150-200 and 500-750 in sim_slice, 850-1100 in sampler_refill
    assert sorted((n, round(t * 1e9)) for n, t in s.idle_gaps) == [
        ("sampler_refill", 250), ("sim_slice", 50), ("sim_slice", 250)]


def test_op_name():
    assert trace_reduce.op_name(
        "%fusion.16 = (u32[1]{0:T(128)}) fusion(u32[2] %key.1), kind=kLoop"
    ) == "fusion.16"
