"""Recovery phases of a killed leader's ranges, from the cluster's event
log: the crash, then `leader_takeover` and `leader_open` on each range."""

from __future__ import annotations

from typing import Optional


def phase_ms(obs, start: str, end: str) -> Optional[float]:
    """Mean over killed-leader ranges of the modeled time from the `start`
    phase to the first `end` event on that range after it."""
    by_rid: dict[int, list] = {}
    for ev in obs.cluster_events:
        if ev["kind"] in ("leader_takeover", "leader_open"):
            by_rid.setdefault(ev["rid"], []).append((ev["t"], ev["kind"]))
    spans = []
    for _k, t_kill, _node, rids in obs.kills:
        for rid in rids:
            evs = [(t, kind) for t, kind in by_rid.get(rid, ()) if t >= t_kill]
            t0 = t_kill if start == "node_crash" else next(
                (t for t, kind in evs if kind == start), None)
            if t0 is None:
                continue
            t1 = next((t for t, kind in evs if kind == end and t >= t0), None)
            if t1 is not None:
                spans.append(t1 - t0)
    return sum(spans) / len(spans) * 1e3 if spans else None
