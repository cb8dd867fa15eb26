"""Per-cell linearizability check of a recorded client history.

Every committed write to a cell gets a dense version from its cohort's
single Paxos log, so the versions are the linearization order of the
writes, and the check verifies that this order agrees with real time
and that strong reads respect it:

W1. two acknowledged writes to one cell never report the same version;
W2. a write invoked after another completed gets a higher version;
R1. a strong read returns no version older than the newest write that
    completed before the read was invoked;
R2. a read returns no version newer than could exist when it completed:
    the highest acked version among writes invoked by then, plus one
    slot for each extra attempt of those writes (an attempt whose ack
    was lost may still have committed);
R3. a read at an acked write's version returns that write's value.

Writes whose outcome is unknown (timeouts, or a rejection after a retry)
widen R2 and never raise R1's floor.  This is the benchmark's own copy of
the checker in `repro/chaos/linearizability.py`, kept here so that no
change to the program can change the yardstick.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import Any, Optional


@dataclass
class HistOp:
    client: str
    kind: str                 # "write" | "read"
    key: str
    col: str
    invoke: float
    response: float
    ok: bool
    version: Optional[int]    # acked write version / read version
    value: Any = None
    resolved: bool = True     # False: the write may or may not have committed
    attempts: int = 1         # client attempts spent (each may commit)


def _cell_violations(cell: tuple, ops: list[HistOp], base: int) -> list[dict]:
    bad: list[dict] = []

    def flag(rule: str, detail: str, op: Optional[HistOp] = None) -> None:
        bad.append({"cell": list(cell), "rule": rule, "detail": detail,
                    "client": op.client if op else None,
                    "t": op.response if op else None})

    acked = [o for o in ops if o.kind == "write" and o.ok
             and o.version is not None]
    unresolved = [o for o in ops if o.kind == "write" and not o.resolved]
    reads = [o for o in ops if o.kind == "read" and o.ok
             and o.version is not None]

    # W1: version uniqueness
    by_version: dict[int, HistOp] = {}
    for w in acked:
        if w.version in by_version:
            flag("W1", f"duplicate acked version {w.version} "
                 f"(clients {by_version[w.version].client}, {w.client})", w)
        else:
            by_version[w.version] = w
        if w.version <= base:
            flag("W1", f"acked version {w.version} <= preload base {base}", w)

    # W2 + R1 share a sweep: walk completions in time order, maintaining
    # the highest version known to be committed by each instant; any write
    # or read *invoked* after that instant must see at least that version.
    completions = sorted(((w.response, w.version) for w in acked))
    comp_times = [t for t, _v in completions]
    comp_pmax = []
    for _t, v in completions:
        comp_pmax.append(max(comp_pmax[-1], v) if comp_pmax else v)

    def floor_at(t: float) -> int:
        i = bisect.bisect_left(comp_times, t)
        return comp_pmax[i - 1] if i else base

    for w in acked:
        f = floor_at(w.invoke)
        if w.version <= f and f > base:
            flag("W2", f"write acked version {w.version} but version {f} "
                 "had already completed before it was invoked", w)

    # R2 ceiling: max acked version invoked by then, plus commit slots for
    # extra attempts (acked writes: attempts-1 beyond the acked commit;
    # unresolved writes: every attempt may have committed)
    acked_by_invoke = sorted((w.invoke, w.version) for w in acked)
    inv_times = [t for t, _v in acked_by_invoke]
    inv_pmax = []
    for _t, v in acked_by_invoke:
        inv_pmax.append(max(inv_pmax[-1], v) if inv_pmax else v)
    extra_slots = sorted([(w.invoke, max(0, w.attempts - 1)) for w in acked]
                         + [(w.invoke, max(1, w.attempts))
                            for w in unresolved])
    slot_times = [t for t, _n in extra_slots]
    slot_psum = []
    for _t, n in extra_slots:
        slot_psum.append((slot_psum[-1] if slot_psum else 0) + n)

    def ceiling_at(t: float) -> int:
        i = bisect.bisect_left(inv_times, t)
        vmax = inv_pmax[i - 1] if i else base
        j = bisect.bisect_left(slot_times, t)
        return vmax + (slot_psum[j - 1] if j else 0)

    for r in reads:
        f = floor_at(r.invoke)
        if r.version < f:
            flag("R1", f"stale read: returned version {r.version} but "
                 f"version {f} completed before the read was invoked", r)
        c = ceiling_at(r.response)
        if r.version > c:
            flag("R2", f"read from the future: returned version "
                 f"{r.version} > ceiling {c}", r)
        w = by_version.get(r.version)
        if w is not None and r.value != w.value:
            flag("R3", f"value mismatch at version {r.version}: read "
                 f"{r.value!r}, write was {w.value!r}", r)
    return bad


def check_linearizability(history: list[HistOp],
                          base_versions: Optional[dict] = None
                          ) -> list[dict]:
    """Check a history; returns a list of violation dicts (empty = clean).

    `base_versions` maps `(key, col)` to the version preloaded before the
    history started (defaults to 0 = cell created by the history)."""
    base_versions = base_versions or {}
    cells: dict[tuple, list[HistOp]] = {}
    for op in history:
        cells.setdefault((op.key, op.col), []).append(op)
    violations: list[dict] = []
    for cell, ops in sorted(cells.items()):
        violations.extend(
            _cell_violations(cell, ops, int(base_versions.get(cell, 0))))
    return violations
