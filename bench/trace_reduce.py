"""Reduction of a JAX profiler trace (`.xplane.pb`) to the benchmark's
device numbers.

- busy: the union of the intervals in which an operation ran on a device,
  inside the traced window, averaged over the devices;
- the sampler's device time: the XLA module executions whose name holds
  the sampler's function name, and how many there were;
- idle gaps: the stretches of the window with no device operation, each
  named by the innermost host span of the benchmark that covers its
  middle (what the host was doing meanwhile).

The window is the host span named `WINDOW` that the harness opens around
the traced window.  Host and device events share the trace's clock.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"


@dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                       # mean over devices
    sampler_s: float = 0.0              # summed over devices
    sampler_runs: int = 0
    device_ops: list = field(default_factory=list)    # [[name, s]], top 10
    idle_gaps: list = field(default_factory=list)     # [[name, s]], top 10


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that `busy` (a union) leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def op_name(hlo: str) -> str:
    """`%fusion.16 = u32[...] fusion(...)` -> `fusion.16`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def reduce_planes(planes, sampler_name: str, host_spans: set[str]
                  ) -> TraceSummary:
    """`planes` are objects with `.name` and `.lines`; lines have `.name`
    and `.events`; events have `.name`, `.start_ns` and `.duration_ns`
    (the shape of `jax.profiler.ProfileData`)."""
    window = None
    spans = []
    device_lines = []
    for plane in planes:
        if plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for name, a, b in _events(line):
                    if name == WINDOW:
                        window = (a, b)
                    elif name in host_spans:
                        spans.append((a, b, name))
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                device_lines.append((lines[OPS_LINE], lines.get(MODULES_LINE)))
    if window is None:
        raise ValueError(f"no host span {WINDOW!r} in the trace")
    lo, hi = window
    out = TraceSummary(window_s=(hi - lo) * 1e-9, devices=len(device_lines),
                       busy_s=0.0)
    if not device_lines:
        return out
    per_op: dict[str, float] = defaultdict(float)
    all_busy = []
    for ops, modules in device_lines:
        mods = sorted((a, b, name.split("(", 1)[0]) for name, a, b in
                      (_events(modules) if modules is not None else ()))
        starts = [m[0] for m in mods]
        iv = []
        for name, a, b in _events(ops):
            c = _clip(a, b, lo, hi)
            if c is None:
                continue
            iv.append(c)
            i = bisect.bisect_right(starts, a) - 1
            module = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            per_op[f"{module}/{op_name(name)}"] += (c[1] - c[0]) * 1e-9
        busy = union(iv)
        all_busy.extend(busy)
        out.busy_s += sum(b - a for a, b in busy) * 1e-9 / len(device_lines)
        if modules is not None:
            for name, a, b in _events(modules):
                c = _clip(a, b, lo, hi)
                if c is not None and sampler_name in name:
                    out.sampler_s += (c[1] - c[0]) * 1e-9
                    out.sampler_runs += 1
    out.device_ops = [[n, s] for n, s in
                      sorted(per_op.items(), key=lambda x: -x[1])[:10]]
    spans.sort(key=lambda s: s[1] - s[0])       # innermost first
    named = []
    for a, b in gaps(union(all_busy), lo, hi):
        mid = (a + b) / 2
        name = next((n for sa, sb, n in spans if sa <= mid <= sb), "none")
        named.append([name, (b - a) * 1e-9])
    out.idle_gaps = sorted(named, key=lambda x: -x[1])[:10]
    return out


def reduce_file(path: Path, sampler_name: str, host_spans: set[str]
                ) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes,
                         sampler_name, host_spans)
