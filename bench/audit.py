"""What decides `correct`: the window's outputs against plain references.

Three layers are compared, each against a reference that imports nothing
of the program:

- the device sampler: every batch the run drew, against the numpy
  reference of the same draw (`sampler_ref.py`);
- the committed state: every write acknowledged to a client is held at
  no less than its acknowledged version by every live replica of its
  cohort once the cluster has settled;
- the read values: the client history passes the per-cell
  linearizability check (`linearizability.py`), and every request the
  window issued was answered.
"""

from __future__ import annotations

from dataclasses import dataclass, field
import numpy as np

from .linearizability import HistOp, check_linearizability
from .sampler_ref import SamplerReference

# Limits, each between the largest reading of sound runs and the smallest
# reading of the control (PERF.md gives both readings).
LIMITS = {
    "sampler_mismatches": 0,        # exact: keys, op kinds, value sizes
    "gap_rel_err": 1e-2,            # widest relative error of a gap
    "lost_acked_writes": 0,
    "lin_violations": 0,
    "unanswered": 0,
}

TAG_BYTES = 24      # a written value's unique tag, kept in the history


class History:
    """Client history, recorded at the client library's entry points.

    Written values get a unique tag at their head (same length as the
    adapter's value), so that a read identifies the write it returns."""

    def __init__(self, sim, strong_reads: bool):
        self.sim = sim
        self.strong_reads = strong_reads
        self.reads: list[tuple] = []    # key, invoke, response, ok, ver, tag
        self.writes: list[tuple] = []   # key, invoke, response, ok, ver, tag,
        #                                 resolved, attempts
        self.pending = 0
        self._n = 0

    def attach(self, client) -> None:
        sim, get, put, cput = (self.sim, client.get, client.put,
                               client.conditional_put)
        cid = client.id.encode()

        def rec_get(key, col, consistent, cb, monotonic=False):
            t0 = sim.now
            self.pending += 1

            def done(res):
                self.pending -= 1
                v = res.value
                self.reads.append((key, t0, sim.now, bool(res.ok),
                                   res.version,
                                   v[:TAG_BYTES] if isinstance(v, bytes)
                                   else v))
                cb(res)
            get(key, col, consistent, done, monotonic)

        def write(send, key, col, value, cb, *args):
            self._n += 1
            tag = (b"%s#%d#" % (cid, self._n)).ljust(TAG_BYTES, b".")
            value = tag + value[TAG_BYTES:]
            t0 = sim.now
            self.pending += 1

            def done(res):
                self.pending -= 1
                attempts = getattr(res, "attempts", 1)
                # a rejected conditional put committed nothing unless an
                # earlier attempt of it may have
                resolved = bool(res.ok) or (
                    res.code.value == "version_mismatch" and attempts == 1)
                self.writes.append((key, t0, sim.now, bool(res.ok),
                                    res.version, tag, resolved, attempts))
                cb(res)
            send(key, col, value, *args, done)

        client.get = rec_get
        client.put = lambda key, col, value, cb: write(
            put, key, col, value, cb)
        client.conditional_put = lambda key, col, value, version, cb: write(
            cput, key, col, value, cb, version)

    def ops(self) -> list[HistOp]:
        out = [HistOp("c", "write", k, "c", t0, t1, ok, ver, tag, res, att)
               for k, t0, t1, ok, ver, tag, res, att in self.writes]
        if self.strong_reads:
            out += [HistOp("c", "read", k, "c", t0, t1, ok, ver, tag)
                    for k, t0, t1, ok, ver, tag in self.reads]
        return out


@dataclass
class Checks:
    values: dict = field(default_factory=dict)
    detail: dict = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(self.values[k] <= LIMITS[k] for k in LIMITS)

    def table(self) -> dict:
        return {k: [self.values[k], LIMITS[k]] for k in LIMITS}


def sampler_readings(batches: list, ref: SamplerReference
                     ) -> tuple[int, float]:
    """(ops whose key, kind or size differ; widest relative gap error)."""
    mismatches, worst = 0, 0.0
    for i, (keys, ops, vsz, gaps) in enumerate(batches):
        rk, ro, rv, rg = ref.batch(i)
        mismatches += int(np.count_nonzero((keys != rk) | (ops != ro)
                                           | (vsz != rv)))
        rg = rg.astype(np.float64)
        err = np.abs(gaps.astype(np.float64) - rg) / np.maximum(rg, 2.0**-23)
        worst = max(worst, float(err.max()))
    return mismatches, worst


def lost_acked_writes(cluster, history: History) -> list[dict]:
    """Acked writes that some live replica of the key's cohort does not
    hold at no less than the acked version."""
    acked: dict[str, int] = {}
    for key, _t0, _t1, ok, ver, *_ in history.writes:
        if ok and ver is not None and ver > acked.get(key, 0):
            acked[key] = ver
    lost = []
    for key, ver in acked.items():
        rid = cluster.range_of(key)
        for m in cluster.members[rid]:
            node = cluster.nodes[m]
            if not node.up:
                continue
            rep = node.replicas.get(rid)
            cell = rep.store.get(key, "c") if rep is not None else None
            held = cell.version if cell is not None else None
            if held is None or held < ver:
                lost.append({"key": key, "node": m, "acked": ver,
                             "held": held})
    return lost


def run_checks(batches: list, ref: SamplerReference, cluster,
               history: History, base_version: int = 1) -> Checks:
    c = Checks()
    mism, gap = sampler_readings(batches, ref)
    c.values["sampler_mismatches"] = mism
    c.values["gap_rel_err"] = gap
    lost = lost_acked_writes(cluster, history)
    c.values["lost_acked_writes"] = len(lost)
    c.detail["lost"] = lost[:5]
    ops = history.ops()
    base = {(op.key, "c"): base_version for op in ops}
    viol = check_linearizability(ops, base)
    c.values["lin_violations"] = len(viol)
    c.detail["violations"] = viol[:5]
    c.values["unanswered"] = history.pending
    return c
