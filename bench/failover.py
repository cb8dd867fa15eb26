"""Client failover and follower catch-up after the kills of an open-loop
fault cell, from the cluster's event log: `client_failover` (a client
follows a range's new leader), `node_restart` and `follower_caught_up`
(a leader takes a restarted replica back into its in-sync set)."""

from __future__ import annotations

from typing import Optional


def _first(events, kind: str, match, t0: float, t1: float):
    return next((ev for ev in events if ev["kind"] == kind
                 and t0 <= ev["t"] < t1 and match(ev)), None)


def failover_ms(obs) -> Optional[float]:
    """Mean over the ranges each kill took the leader of: modeled ms from
    the kill to the first client following the range's new leader."""
    events = obs.cluster_events
    spans = []
    for _k, t_kill, _node, rids in obs.kills:
        for rid in rids:
            ev = _first(events, "client_failover",
                        lambda e, rid=rid: e["rid"] == rid, t_kill, 1e300)
            if ev is not None:
                spans.append(ev["t"] - t_kill)
    return sum(spans) / len(spans) * 1e3 if spans else None


def catchups(obs) -> list[list[dict]]:
    """For each kill whose node was restarted: the first `follower_caught_up`
    of each of the restarted node's replicas before its next crash, with
    the restart time under `t_restart`."""
    events = obs.cluster_events
    out = []
    for _k, t_kill, node, _rids in obs.kills:
        restart = _first(events, "node_restart",
                         lambda e: e["node"] == node, t_kill, 1e300)
        if restart is None:
            continue
        t_r = restart["t"]
        nxt = _first(events, "node_crash", lambda e: e["node"] == node,
                     t_r, 1e300)
        t_end = nxt["t"] if nxt is not None else 1e300
        first: dict[int, dict] = {}
        for ev in events:
            if ev["kind"] == "follower_caught_up" and ev["node"] == node \
                    and t_r <= ev["t"] < t_end and ev["rid"] not in first:
                first[ev["rid"]] = dict(ev, t_restart=t_r)
        if first:
            out.append(list(first.values()))
    return out


def catchup_ms(obs) -> Optional[float]:
    """Mean over the restarted nodes' replicas: modeled ms from the node's
    restart to its replica being in sync with the range's leader again."""
    spans = [ev["t"] - ev["t_restart"] for per in catchups(obs) for ev in per]
    return sum(spans) / len(spans) * 1e3 if spans else None


def catchup_records_per_restart(obs) -> Optional[float]:
    """Records the leaders sent the restarted node's replicas to catch
    them up, per restart."""
    per = catchups(obs)
    return sum(ev["records"] for p in per for ev in p) / len(per) \
        if per else None
