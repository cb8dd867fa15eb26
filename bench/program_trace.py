"""The program's host profile, read from what a traced run leaves.

The program (`repro.obs.hostprof.HostProfile`, attached to the simulator
for the traced window) gives two things:

- its `summary()`: self time per layer, heap and message counters;
  `host_metrics` turns it into the per-layer numbers per completed op;
- one `obs.sampler_batch` annotation per sampler batch in the profiler
  trace, whose arguments are each layer's self time since the previous
  batch; `idle_gap_layers` splits each of the window's longest device
  idle gaps by the first annotation that opens after it begins, on the
  trace's own clock.
"""

from __future__ import annotations

from pathlib import Path
from typing import Optional

from . import trace_reduce

BATCH_SPAN = "obs.sampler_batch"
# a trace places device operations up to about a millisecond off the
# host's annotations, so the device programs of one batch can appear to
# run before its annotation opens: gaps shorter than this, such as those
# between the programs of one batch, are not split
CLOCK_SKEW_NS = 2_000_000
SPAN_LAYERS = ("sched", "net", "queues", "node", "protocol", "client",
               "workload", "gc")


def host_metrics(summary: Optional[dict], ops_ok: int) -> dict:
    """Per-layer numbers of a host profile summary; empty where there is
    no profile or no completed op."""
    if not summary or not ops_ok:
        return {}
    ns = summary["self_ns"]
    out = {f"span_us_per_op.{k}": ns[k] / 1e3 / ops_ok for k in SPAN_LAYERS}
    if summary["sampler_batches"]:
        out["sampler_host_wait_us_per_batch"] = \
            ns["sampler_wait"] / 1e3 / summary["sampler_batches"]
    if summary["pops"]:
        out["cancelled_pop_pct"] = \
            summary["cancelled_pops"] / summary["pops"] * 100.0
        out["heap_depth_mean"] = summary["heap_depth_sum"] / summary["pops"]
    out["msgs_per_op"] = summary["msgs_sent"] / ops_ok
    return out


def _batch_starts(planes) -> list[tuple[int, dict]]:
    """(start ns, arguments) of each batch annotation, in time order."""
    out = []
    for plane in planes:
        if not plane.name.startswith(trace_reduce.HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            out.extend((e.start_ns, dict(e.stats)) for e in line.events
                       if e.name == BATCH_SPAN)
    return sorted(out, key=lambda s: s[0])


def idle_gap_layers(planes, top: int = 10) -> list[dict]:
    """The `top` longest stretches of the window in which the device ran
    nothing, longest first, each with the host's self time per layer (µs)
    in it: the layers of the first batch annotation that opens after the
    gap begins, less that annotation's `sampler_wait`, which is the
    previous batch's wait and holds that batch's device run.  A gap that
    no annotation follows (the window's end), or one shorter than
    `CLOCK_SKEW_NS`, has `layers_us` None."""
    planes = list(planes)
    window, busy = None, []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith(trace_reduce.HOST_PLANE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_reduce.WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX) \
                and trace_reduce.OPS_LINE in lines:
            busy.extend((e.start_ns, e.start_ns + e.duration_ns)
                        for e in lines[trace_reduce.OPS_LINE].events)
    if window is None:
        raise ValueError(f"no host span {trace_reduce.WINDOW!r} in the trace")
    batches = _batch_starts(planes)
    gaps = trace_reduce.gaps(trace_reduce.union(busy), *window)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        after = next((meta for start, meta in batches if start >= a), None)
        layers = None
        if after is not None and b - a >= CLOCK_SKEW_NS:
            layers = {k[:-3]: v for k, v in after.items()
                      if k.endswith("_us") and k != "sampler_wait_us"}
        out.append({"gap_s": (b - a) * 1e-9, "layers_us": layers})
    return out


def read_file(path: Path) -> list[dict]:
    from jax.profiler import ProfileData
    return idle_gap_layers(ProfileData.from_file(str(path)).planes)
