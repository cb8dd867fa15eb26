"""The rest of a run, without the look for a chip, on the CPU, with the
timed path broken underneath: each fault the cells can have makes
`correct` come out false."""

import time

import numpy as np
from bench import cells, harness
from repro.core import replica, storage
from repro.core.types import ErrorCode, Result
from repro.workload import generators


def run(name="s9-strong-closed32"):
    return harness.run_cell(cells.load_cell(name), seed=12345,
                            seconds=1.0, trace=False,
                            t_setup0=time.perf_counter())


def test_answer_altered_where_produced(monkeypatch):
    """The leader answers reads of written keys with another value."""
    inner = replica.CohortReplica._read_one

    def read_one(self, key, colname, consistent, reply):
        def altered(res):
            if res.ok and res.version and res.version > 1:
                res = Result(ErrorCode.OK, value=b"altered" + res.value[7:],
                             version=res.version)
            reply(res)
        inner(self, key, colname, consistent, altered)
    monkeypatch.setattr(replica.CohortReplica, "_read_one", read_one)
    out = run()
    assert not out["correct"]
    assert out["checks"]["lin_violations"][0] > 0
    assert "R3" in {v["rule"] for v in out["_detail"]["violations"]}


def test_state_left_unchanged(monkeypatch):
    """Writes after the preload are acked but leave the store unchanged."""
    inner = storage.Store.apply

    def apply(self, rec):
        if rec.lsn is not None and all(
                v > 1 for _c, _v, v in getattr(rec, "columns", ())):
            return
        inner(self, rec)
    monkeypatch.setattr(storage.Store, "apply", apply)
    out = run()
    assert not out["correct"]
    assert out["checks"]["lost_acked_writes"][0] > 0


def test_half_of_sampler_batch_left_out(monkeypatch):
    """The sampler fills the second half of each batch with the first."""
    inner = generators._sample_batch

    def half(*args, **kw):
        outs = inner(*args, **kw)
        n = outs[0].shape[0] // 2
        return tuple(np.concatenate([np.asarray(x)[:n]] * 2) for x in outs)
    monkeypatch.setattr(generators, "_sample_batch", half)
    out = run("ycsb-b-closed32")
    assert not out["correct"]
    assert out["checks"]["sampler_mismatches"][0] > 0
