"""Paper-§9 experiment runner: Spinnaker vs the Cassandra baseline.

    PYTHONPATH=src python benchmarks/spinnaker_bench.py \
        --scenario all [--quick] [--out BENCH_spinnaker.json]

Scenarios:

- `fig8`    — read/write latency + throughput under a steady 80/15 YCSB-
  style zipfian mix, for Spinnaker strong reads, Spinnaker timeline reads,
  Cassandra quorum, and Cassandra eventual consistency;
- `fig9`    — kill the leader of range 0 mid-load with the fault-schedule
  DSL and record sliding-window write availability (writes must resume
  without manual intervention once a follower takes over);
- `fig10`   — same failure, timeline-read availability (reads keep being
  served by the surviving replicas throughout);
- `saturation` — open-loop write-only rate ramps per disk class (§C
  methodology): batch=off vs adaptive proposal-batching curves, locating
  the saturation knee each way, plus an overload-tail check (post-knee
  throughput must not collapse — client retry backoff's job).  This is
  the measurement surface future perf PRs regress against;
- `rebalance` — elastic range management under zipfian write load: the
  hottest range live-splits, one replica migrates, and the range leader
  is killed mid-migration.  Gates: no lost acknowledged writes, writes
  continuing on both child ranges, the migration resolving unaided, and
  write availability >= 99% through it all;
- `txn`     — cross-range transactions (PR 4): a balance-transfer mix is
  run three ways — all single-cohort (the §8.2 fast path), all
  cross-range (Paxos-backed 2PC), and a mixed run with the 2PC
  coordinator killed mid-transaction.  Records the cross/local commit
  latency ratio, the abort rate under contention, and the
  leader-kill-mid-2PC audit (zero acknowledged-but-lost transactions,
  zero partial commits — the strong-read balance sum must close);
- `breakdown` — write-path latency decomposition from the sim-time span
  tracer: per-stage (client queue, request net, cpu, batch wait, WAL
  force, commit wait, reply net) contributions to the strong-write p50,
  Spinnaker vs Cassandra quorum, plus the trace-completeness audits
  under leader-kill and mid-2PC coordinator-kill schedules and the
  tracing-overhead measurement (full sampling must cost < 5% write
  throughput; it models zero sim-time, so the expected cost is exactly
  zero).  `--report` pretty-prints the committed block;
- `profile` — component-attributed cluster resource profile (PR 8):
  per-node x per-component CPU/disk/network busy-time shares for
  Spinnaker vs Cassandra-eventual at a fixed matched load, per-range
  heat, and a utilization timeline.  Gates: attribution sums to the
  measured busy time within 5%, and the profiled run is bit-identical
  to an unprofiled one (the profiler models zero sim-time).  The fixed
  config is --quick-independent so `benchmarks/perf_diff.py` can ratchet
  fresh runs against the committed section;
- `chaos`   — the robustness gate (PR 7): eight seeded gray-failure
  schedules (crashes, partitions incl. one-way, lossy/dup/slow links,
  degraded disks/CPUs, ZK session flaps) driven against concurrent
  client histories, each audited for linearizability, availability
  (majority-healthy windows must keep serving probe writes within the
  recovery bound), lost acknowledged writes, and trace completeness;
  plus the signature minority-partitioned-leader pair — with leader
  leases the cohort fails over within `lease + election` and the old
  leader self-fences, without them it stalls until the partition heals —
  and the lease-read comparison (leaseholder strong reads serve locally,
  so their p50 must not exceed the read-index path's);
- `figs8-10`— figs 8, 9, 10;
- `all`     — everything above in one JSON artifact;
- `regress` — re-measure fig8 write throughput and a capped saturation
  sweep, compare against the committed `--out` file, exit 1 on a >10%
  write-throughput regression (the smoke.sh gate; does not overwrite).

Emits `BENCH_spinnaker.json` plus claim checks against the paper's
headline: comparable read latency, writes within ~5-10% of eventual
consistency's throughput cost envelope, post-failover recovery, and the
batching win at the knee (peak write throughput ≥ 25% over batch=off
with light-load p50 within 10%).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.obs import format_profile_report  # noqa: E402
from repro.workload import (ExperimentConfig, WorkloadSpec,  # noqa: E402
                            run_cassandra_breakdown, run_cassandra_profiled,
                            run_cassandra_workload, run_spinnaker_breakdown,
                            run_spinnaker_chaos, run_spinnaker_minority_leader,
                            run_spinnaker_profiled, run_spinnaker_rebalance,
                            run_spinnaker_saturation, run_spinnaker_txn,
                            run_spinnaker_workload)

LEADER_KILL = """
# Fig. 9/10: kill whichever node currently leads range 0, mid-load;
# bring it back later.  No operator intervention in between.
at {t_kill}s crash leader of 0
at {t_back}s restart crashed
"""


def base_spec(quick: bool) -> WorkloadSpec:
    return WorkloadSpec(
        num_keys=1000 if quick else 5000,
        key_dist="zipfian", zipf_theta=0.99,
        read_frac=0.80, write_frac=0.15, rmw_frac=0.03, cond_frac=0.02,
        value_size=4096)


def base_cfg(quick: bool, seed: int = 0) -> ExperimentConfig:
    # 16 closed-loop clients put the cluster at ~60-70% peak node
    # utilization — the load point where throughput claims mean something
    # (the paper's Fig. 8 measures under multi-client load, not an idle
    # cluster) and where batching/coalescing actually engage.  8 ranges
    # per node pre-splits the keyspace so zipfian hot keys land on
    # different range leaders (§2.1 runs many ranges per node).
    return ExperimentConfig(
        n_nodes=5, disk="ssd", seed=seed,
        n_clients=16 if quick else 32,
        ranges_per_node=8,
        warmup=0.5 if quick else 2.0,
        duration=3.0 if quick else 15.0,
        preload_cap=1000 if quick else 5000)


def run_fig8(quick: bool) -> dict:
    spec, cfg = base_spec(quick), base_cfg(quick)
    print("fig8: steady-state comparison ...", flush=True)
    out = {
        "spinnaker_strong": run_spinnaker_workload(
            spec, cfg, consistent_reads=True),
        "spinnaker_timeline": run_spinnaker_workload(
            spec, cfg, consistent_reads=False, monotonic=True),
        "cassandra_quorum": run_cassandra_workload(spec, cfg, quorum=True),
        "cassandra_eventual": run_cassandra_workload(spec, cfg, quorum=False),
    }
    for name, r in out.items():
        print(f"  {name}: reads p50={r['reads']['p50_ms']:.2f}ms "
              f"p99={r['reads']['p99_ms']:.2f}ms "
              f"writes p50={r['writes']['p50_ms']:.2f}ms "
              f"tput={r['throughput']:.0f}/s", flush=True)
    return out


def sat_spec() -> WorkloadSpec:
    """Write-only uniform mix: isolates the replication write path the way
    §C's saturation runs do (reads would only dilute the knee)."""
    return WorkloadSpec(num_keys=1000, key_dist="uniform",
                        read_frac=0.0, write_frac=1.0, rmw_frac=0.0,
                        cond_frac=0.0, value_size=1024)


# server-side admission gate for the saturation ramps: shed once a node's
# CPU backlog (queue + staged ingress work) exceeds this many seconds of
# service time.  ~2ms keeps the pipeline full at the knee while cutting
# the congestive collapse past it (clients back off on OVERLOADED instead
# of piling retries onto a saturated leader).
SAT_ADMISSION_LIMIT = 2e-3


def sat_cfg(disk: str, batch: str, seed: int = 0) -> ExperimentConfig:
    # batch="off" disables the whole batching stack — leader proposal
    # batching AND server-side ingress batching — so the off-vs-adaptive
    # curves keep measuring what batching buys end-to-end.  (Ingress
    # batching alone moved the off knee from ~24k/s to ~85k/s; with it on
    # in both arms the comparison would only see the residual proposal-
    # batching delta, not the stack.)
    return ExperimentConfig(n_nodes=5, disk=disk, batch=batch, seed=seed,
                            ingress_batch=(batch != "off"),
                            admission_limit=SAT_ADMISSION_LIMIT,
                            preload_cap=100)


# the ramps straddle the post-PR-10 knees: batch=off (stack disabled)
# knees ~25-40k/s, adaptive ~85-90k/s, so the top rate gives both arms a
# ~1.5x-knee retention probe point
SAT_RATES_QUICK = [5000, 30000, 60000, 90000, 135000]
SAT_RATES = [2000, 10000, 25000, 40000, 60000, 80000,
             100000, 120000, 150000]


def _post_knee(curve: dict) -> dict:
    """Post-knee retention for one ramp: throughput at the knee (the
    offered rate achieving peak) vs at ~1.5x the knee rate.  With
    admission control shedding past the knee this should hold >= 0.70
    instead of collapsing into congestive retry storms.  When the ramp
    tops out before 1.5x the knee, the highest offered rate stands in
    (recorded so the ratio is honest about its load point)."""
    pts = curve["points"]
    knee = max(pts, key=lambda p: p["achieved_tput"])
    target = 1.5 * knee["offered_rate"]
    past = [p for p in pts if p["offered_rate"] >= target]
    probe = past[0] if past else pts[-1]
    at_knee = knee["achieved_tput"]
    at_probe = probe["achieved_tput"]
    return {
        "knee_rate": knee["offered_rate"],
        "tput_at_knee": at_knee,
        "probe_rate": probe["offered_rate"],
        "tput_at_1.5x_knee": at_probe,
        "post_knee_retention": at_probe / max(at_knee, 1e-9),
        "probe_at_1.5x": bool(past),
        "shed_total": sum(p.get("shed", 0) for p in pts),
    }


def check_saturation(off: dict, adaptive: dict,
                     admission: bool = True) -> dict:
    """Acceptance surface: the batching stack (leader proposal batching +
    server ingress batching, the adaptive arm) must buy >= 25% peak write
    throughput at the knee over the stack-disabled off arm without
    costing > 10% p50 at light load, and
    the overload tail (throughput at the highest offered rate, past the
    knee) must hold >= 60% of the peak — retry backoff keeps overload
    from collapsing into congestive retry storms.  With admission
    control on (the default for the bench ramps), the post-knee
    retention — throughput at ~1.5x the knee rate over throughput at
    the knee — must additionally hold >= 0.70 on both batch arms."""
    p50_off = off["points"][0]["write_p50_ms"]
    p50_ad = adaptive["points"][0]["write_p50_ms"]
    gain = adaptive["peak_write_tput"] / max(off["peak_write_tput"], 1e-9)
    ratio = p50_ad / max(p50_off, 1e-9)
    tail_off = off["points"][-1]["achieved_tput"] / \
        max(off["peak_write_tput"], 1e-9)
    tail_ad = adaptive["points"][-1]["achieved_tput"] / \
        max(adaptive["peak_write_tput"], 1e-9)
    pk_off, pk_ad = _post_knee(off), _post_knee(adaptive)
    retention_ok = (pk_off["post_knee_retention"] >= 0.70
                    and pk_ad["post_knee_retention"] >= 0.70)
    return {
        "peak_write_tput_off": off["peak_write_tput"],
        "peak_write_tput_adaptive": adaptive["peak_write_tput"],
        "peak_gain": gain,
        "light_load_p50_off_ms": p50_off,
        "light_load_p50_adaptive_ms": p50_ad,
        "light_load_p50_ratio": ratio,
        "mean_batch_records": adaptive["mean_batch_records"],
        "overload_tail_off": tail_off,
        "overload_tail_adaptive": tail_ad,
        "tail_ok": bool(tail_off >= 0.6 and tail_ad >= 0.6),
        "post_knee_off": pk_off,
        "post_knee_adaptive": pk_ad,
        "admission_enabled": bool(admission),
        "retention_ok": bool(retention_ok or not admission),
        "ok": bool(gain >= 1.25 and ratio <= 1.10
                   and tail_off >= 0.6 and tail_ad >= 0.6
                   and (retention_ok or not admission)),
    }


def run_saturation(quick: bool) -> dict:
    rates = SAT_RATES_QUICK if quick else SAT_RATES
    dwell = 1.0 if quick else 2.0
    out = {}
    for disk in ("ssd", "mem", "hdd"):
        curves = {}
        for batch in ("off", "adaptive"):
            print(f"saturation: disk={disk} batch={batch} ...", flush=True)
            curves[batch] = run_spinnaker_saturation(
                sat_spec(), sat_cfg(disk, batch), rates=rates,
                dwell=dwell, settle=0.3)
        check = check_saturation(curves["off"], curves["adaptive"])
        out[disk] = {"off": curves["off"], "adaptive": curves["adaptive"],
                     "check": check}
        print(f"  {disk}: knee off={check['peak_write_tput_off']:.0f}/s "
              f"adaptive={check['peak_write_tput_adaptive']:.0f}/s "
              f"(gain {check['peak_gain']:.2f}x, "
              f"light p50 ratio {check['light_load_p50_ratio']:.2f}, "
              f"meanB={check['mean_batch_records']:.1f}) "
              f"{'ok' if check['ok'] else 'FAIL'}", flush=True)
    return out


def run_regression_gate(committed_path: str) -> int:
    """smoke.sh gate: compare a fresh fig8 write-throughput measurement and
    a capped saturation quick-sweep against the committed artifact."""
    path = Path(committed_path)
    if not path.exists():
        print(f"regress: no committed {committed_path}; nothing to gate")
        return 0
    committed = json.loads(path.read_text())
    rc = 0
    # 1. fig8 write throughput, same config as the committed quick run
    want = committed.get("fig8", {}).get("spinnaker_strong", {}) \
        .get("writes", {}).get("throughput")
    if want:
        spec, cfg = base_spec(True), base_cfg(True)
        got = run_spinnaker_workload(spec, cfg, consistent_reads=True)
        tput = got["writes"]["throughput"]
        print(f"regress fig8: write tput {tput:.0f}/s vs committed "
              f"{want:.0f}/s ({tput / want:.2f}x)")
        if tput < 0.9 * want:
            print("FAIL: fig8 write throughput regressed >10%")
            rc = 1
        # claims ratchet: re-measure the paper-claim ratios fresh and hold
        # them to the committed ones (one-way: the write gap may only
        # shrink, throughput may only grow, 5% tolerance) plus the
        # absolute acceptance envelope.  Old artifacts stored claims as a
        # list of strings; the ratchet starts once a structured block is
        # committed.
        ce = run_cassandra_workload(spec, cfg, quorum=False)
        cq = run_cassandra_workload(spec, cfg, quorum=True)
        fresh = check_paper_claims({"spinnaker_strong": got,
                                    "cassandra_eventual": ce,
                                    "cassandra_quorum": cq})
        print(f"regress claims: read {fresh['read_vs_quorum_ratio']:.3f} "
              f"write {fresh['write_p50_ratio']:.3f} "
              f"tput {fresh['throughput_ratio']:.3f}")
        if not fresh["ok"]:
            print(f"FAIL: fresh claim ratios outside the acceptance "
                  f"envelope {fresh['targets']}")
            rc = 1
        base = committed.get("claims")
        if isinstance(base, dict):
            if fresh["write_p50_ratio"] > 1.05 * base["write_p50_ratio"]:
                print(f"FAIL: write p50 ratio ratchet "
                      f"{base['write_p50_ratio']:.3f} -> "
                      f"{fresh['write_p50_ratio']:.3f} (>5% slip)")
                rc = 1
            if fresh["read_vs_quorum_ratio"] > \
                    1.05 * base["read_vs_quorum_ratio"]:
                print(f"FAIL: read vs quorum ratio ratchet "
                      f"{base['read_vs_quorum_ratio']:.3f} -> "
                      f"{fresh['read_vs_quorum_ratio']:.3f} (>5% slip)")
                rc = 1
            if fresh["throughput_ratio"] < \
                    0.95 * base["throughput_ratio"]:
                print(f"FAIL: throughput ratio ratchet "
                      f"{base['throughput_ratio']:.3f} -> "
                      f"{fresh['throughput_ratio']:.3f} (>5% slip)")
                rc = 1
    # 2. capped saturation quick-sweep: batching must still buy throughput
    rates = SAT_RATES_QUICK[:3]
    off = run_spinnaker_saturation(sat_spec(), sat_cfg("ssd", "off"),
                                   rates=rates, dwell=0.6, settle=0.2)
    ad = run_spinnaker_saturation(sat_spec(), sat_cfg("ssd", "adaptive"),
                                  rates=rates, dwell=0.6, settle=0.2)
    print(f"regress saturation (capped @ {rates[-1]}/s): "
          f"off={off['peak_write_tput']:.0f}/s "
          f"adaptive={ad['peak_write_tput']:.0f}/s")
    if ad["peak_write_tput"] < 1.15 * off["peak_write_tput"]:
        print("FAIL: adaptive batching lost its throughput edge")
        rc = 1
    # post-knee retention on the capped sweep (admission control's job);
    # only gated where the cap leaves a true ~1.5x-knee probe point
    for name, curve in (("off", off), ("adaptive", ad)):
        pk = _post_knee(curve)
        if pk["probe_at_1.5x"] and pk["post_knee_retention"] < 0.70:
            print(f"FAIL: batch={name} post-knee retention "
                  f"{pk['post_knee_retention']:.2f} < 0.70 "
                  f"(knee {pk['tput_at_knee']:.0f}/s @ "
                  f"{pk['knee_rate']}/s, probe {pk['tput_at_1.5x_knee']:.0f}"
                  f"/s @ {pk['probe_rate']}/s)")
            rc = 1
        elif pk["probe_at_1.5x"]:
            print(f"regress retention batch={name}: "
                  f"{pk['post_knee_retention']:.2f} >= 0.70 ok")
    want_sat = committed.get("saturation", {}).get("ssd", {}) \
        .get("check", {}).get("peak_write_tput_adaptive")
    if want_sat and ad["peak_write_tput"] < 0.9 * min(want_sat, rates[-1]):
        print(f"FAIL: capped adaptive peak {ad['peak_write_tput']:.0f}/s "
              f"regressed >10% vs committed {want_sat:.0f}/s (capped)")
        rc = 1
    return rc


def rebalance_spec(quick: bool) -> WorkloadSpec:
    """Write-heavy zipfian mix: the shape that concentrates load on one
    range and makes it worth splitting."""
    return WorkloadSpec(
        num_keys=1000 if quick else 5000,
        key_dist="zipfian", zipf_theta=0.99,
        read_frac=0.2, write_frac=0.8, rmw_frac=0.0, cond_frac=0.0,
        value_size=1024)


def run_rebalance(quick: bool) -> dict:
    cfg = ExperimentConfig(
        n_nodes=5, disk="ssd", seed=2, driver="open",
        open_rate=1500 if quick else 3000,
        warmup=0.5 if quick else 1.0,
        duration=8.0 if quick else 20.0,
        window=0.5, preload_cap=500 if quick else 2000)
    print("rebalance: live split + migration + leader kill under zipfian "
          "write load ...", flush=True)
    r = run_spinnaker_rebalance(rebalance_spec(quick), cfg, kill_leader=True)
    rb = r["rebalance"]
    wins = [w for w in r["timeline"]["write"] if w["throughput"] > 0]
    rb["min_window_write_tput"] = min(
        (w["throughput"] for w in r["timeline"]["write"]), default=0.0)
    rb["write_p99_ms"] = r["writes"]["p99_ms"]
    rb["nonzero_write_windows"] = len(wins)
    rb["total_write_windows"] = len(r["timeline"]["write"])
    print(f"  ranges {rb['n_ranges_start']} -> {rb['n_ranges_end']}, "
          f"availability {rb['write_availability']:.4f}, "
          f"write p99 {rb['write_p99_ms']:.1f}ms, "
          f"lost acked writes: {len(rb['lost_acked_writes'])}", flush=True)
    return r


def check_rebalance(r: dict) -> dict:
    rb = r["rebalance"]
    return {
        "no_lost_acked_writes": not rb["lost_acked_writes"],
        "split_completed": rb["n_ranges_end"] > rb["n_ranges_start"],
        "all_ranges_serving_writes": rb["all_ranges_serving_writes"],
        "migration_resolved": not rb["unresolved_migrations"],
        "availability_ok": rb["write_availability"] >= 0.99,
        "ok": bool(not rb["lost_acked_writes"]
                   and rb["n_ranges_end"] > rb["n_ranges_start"]
                   and rb["all_ranges_serving_writes"]
                   and not rb["unresolved_migrations"]
                   and rb["write_availability"] >= 0.99),
    }


def txn_spec(quick: bool) -> WorkloadSpec:
    """Uniform read/transfer mix: uniform keys keep CAS contention
    moderate so the abort-rate gate measures the protocol, not a zipfian
    hot key; transfers are zero-sum so the balance audit closes."""
    return WorkloadSpec(
        num_keys=400 if quick else 2000, key_dist="uniform",
        read_frac=0.2, write_frac=0.0, rmw_frac=0.0, cond_frac=0.0,
        txn_frac=0.8, value_size=64)


def txn_cfg(quick: bool) -> ExperimentConfig:
    return ExperimentConfig(
        n_nodes=5, disk="ssd", seed=3,
        n_clients=8 if quick else 16,
        warmup=0.5 if quick else 1.0,
        duration=4.0 if quick else 12.0,
        window=0.5, preload_cap=400 if quick else 2000)


def _txn_summary(r: dict) -> dict:
    """Per-run block for the artifact: latency populations + audit."""
    return {"txn_local": r["txn_local"], "txn_cross": r["txn_cross"],
            "reads": r["reads"], "throughput": r["throughput"],
            "txn": r["txn"]}


def run_txn(quick: bool) -> dict:
    spec, cfg = txn_spec(quick), txn_cfg(quick)
    print("txn: single-cohort fast-path baseline ...", flush=True)
    local = run_spinnaker_txn(spec, cfg, cross_frac=0.0)
    print(f"  local p50={local['txn_local']['p50_ms']:.2f}ms "
          f"(2pc sends: {local['txn']['txn2_issued']})", flush=True)
    print("txn: all-cross 2PC ...", flush=True)
    cross = run_spinnaker_txn(spec, cfg, cross_frac=1.0)
    print(f"  cross p50={cross['txn_cross']['p50_ms']:.2f}ms "
          f"abort rate {cross['txn']['txn_abort_rate']:.3f}", flush=True)
    d = cfg.duration
    sched = (f"at {d * 0.3:.2f}s crash txn coordinator\n"
             f"at {d * 0.75:.2f}s restart crashed")
    if not quick:
        sched += f"\nat {d * 0.55:.2f}s crash txn coordinator"
    print("txn: mixed run with mid-2PC coordinator kill ...", flush=True)
    kill = run_spinnaker_txn(spec, cfg, cross_frac=0.5, schedule=sched)
    ka = kill["txn"]
    print(f"  kill run: {ka['acked_txns_ledgered']} acked audited, "
          f"{len(ka['lost_acked_txns'])} lost, partial={ka['partial_commit']}"
          f", abort rate {ka['txn_abort_rate']:.3f}", flush=True)
    ratio = cross["txn_cross"]["p50_ms"] / max(local["txn_local"]["p50_ms"],
                                               1e-9)
    return {"local": _txn_summary(local), "cross": _txn_summary(cross),
            "kill": {**_txn_summary(kill),
                     "fault_events": kill.get("fault_events", []),
                     "timeline": kill.get("timeline", {})},
            "cross_local_p50_ratio": ratio}


def check_txn(r: dict) -> dict:
    """Acceptance surface: the fast path must never engage 2PC machinery,
    the coordinator-kill audit must close (zero acked-but-lost, zero
    partial commits), the contention abort rate stays bounded, and the
    cross/local latency ratio is recorded (2PC pays ~one extra consensus
    round plus the decision)."""
    ka = r["kill"]["txn"]
    la = r["local"]["txn"]
    out = {
        "fastpath_no_2pc": la["txn2_issued"] == 0
        and la["server"]["prepares"] == 0,
        "fastpath_p50_ms": r["local"]["txn_local"]["p50_ms"],
        "cross_p50_ms": r["cross"]["txn_cross"]["p50_ms"],
        "cross_local_p50_ratio": r["cross_local_p50_ratio"],
        "no_lost_acked_txns": not ka["lost_acked_txns"],
        "no_partial_commit": not ka["partial_commit"],
        # gates too: a skipped coordinator kill (honest no-op) would make
        # the zero-lost audit vacuous
        "killed_mid_2pc": any("crash node" in e
                              for e in r["kill"]["fault_events"]),
        "all_intents_resolved": not ka["unresolved_intents"]
        and ka["leftover_locks"] == 0,
        "abort_rate": ka["txn_abort_rate"],
        "abort_rate_ok": ka["txn_abort_rate"] <= 0.25,
    }
    out["ok"] = bool(out["fastpath_no_2pc"] and out["no_lost_acked_txns"]
                     and out["no_partial_commit"] and out["killed_mid_2pc"]
                     and out["all_intents_resolved"]
                     and out["abort_rate_ok"])
    return out


CHAOS_SEEDS = 8


def run_chaos(quick: bool) -> dict:
    """Chaos gate (PR 7): seeded gray-failure schedules with full audits,
    the minority-partitioned-leader lease-vs-stall pair, and the
    lease-read latency comparison."""
    duration = 10.0 if quick else 18.0
    runs = []
    for seed in range(CHAOS_SEEDS):
        print(f"chaos: schedule seed={seed} ...", flush=True)
        r = run_spinnaker_chaos(seed=seed, duration=duration)
        rb = r["client_robustness"]
        print(f"  {'ok' if r['ok'] else 'FAIL'}: {r['history_ops']} history "
              f"ops, {len(r['fault_events'])} faults, "
              f"{rb['retries']} retries, lin="
              f"{'clean' if r['linearizability']['ok'] else 'VIOLATED'}, "
              f"avail={'ok' if r['availability']['ok'] else 'VIOLATED'}, "
              f"lost={len(r['lost_acked_writes'])}", flush=True)
        runs.append(r)

    print("chaos: minority-partitioned leader, leases ON ...", flush=True)
    on = run_spinnaker_minority_leader(lease_enabled=True)
    print(f"  failover={on['failover_s']}s first_ack_gap="
          f"{on['first_ack_gap_s']}s old leader {on['old_leader_role']} "
          f"lease_valid={on['old_leader_lease_valid']}", flush=True)
    print("chaos: minority-partitioned leader, leases OFF ...", flush=True)
    off = run_spinnaker_minority_leader(lease_enabled=False)
    print(f"  failover={off['failover_s']} stalled_until_heal="
          f"{off['stalled_until_heal']} first_ack_gap="
          f"{off['first_ack_gap_s']}s", flush=True)

    # lease-holder strong reads serve locally (zero round-trips); with
    # leases off every strong read pays the read-index majority round
    print("chaos: strong-read p50, lease vs read-index ...", flush=True)
    spec = WorkloadSpec(num_keys=1000, key_dist="zipfian", zipf_theta=0.99,
                        read_frac=0.95, write_frac=0.05, rmw_frac=0.0,
                        cond_frac=0.0, value_size=1024)
    rcfg = base_cfg(quick, seed=2)
    lease_on = run_spinnaker_workload(spec, rcfg, consistent_reads=True)
    rcfg_off = dataclasses.replace(rcfg, lease_enabled=False)
    lease_off = run_spinnaker_workload(spec, rcfg_off, consistent_reads=True)
    reads = {
        "lease_on_read_p50_ms": lease_on["reads"]["p50_ms"],
        "lease_off_read_p50_ms": lease_off["reads"]["p50_ms"],
        "ratio": lease_on["reads"]["p50_ms"]
        / max(lease_off["reads"]["p50_ms"], 1e-9),
    }
    print(f"  lease on p50={reads['lease_on_read_p50_ms']:.3f}ms, "
          f"off p50={reads['lease_off_read_p50_ms']:.3f}ms "
          f"(ratio {reads['ratio']:.2f})", flush=True)
    return {"runs": runs, "minority_leader": {"lease_on": on,
                                             "lease_off": off},
            "lease_reads": reads}


def check_chaos(r: dict) -> dict:
    """Acceptance surface: every seeded schedule passes all four audits;
    the minority-partitioned leader fails over within lease + election
    with leases (and provably self-fences) but stalls until heal without;
    lease-holder strong reads are no slower than the read-index path."""
    runs = r["runs"]
    on = r["minority_leader"]["lease_on"]
    off = r["minority_leader"]["lease_off"]
    failover_bound = on["lease_duration_s"] + 1.0
    out = {
        "n_schedules": len(runs),
        "all_schedules_ok": all(x["ok"] for x in runs),
        "lin_violations": sum(len(x["linearizability"]["violations"])
                              for x in runs),
        "avail_violations": sum(len(x["availability"]["violations"])
                                for x in runs),
        "lost_acked_writes": sum(len(x["lost_acked_writes"]) for x in runs),
        "failover_s_with_lease": on["failover_s"],
        "failover_bound_s": failover_bound,
        "failover_within_bound": on["failover_s"] is not None
        and on["failover_s"] <= failover_bound,
        "old_leader_fenced": not on["old_leader_lease_valid"]
        and on["old_leader_role"] != "LEADER",
        "stalls_without_lease": off["stalled_until_heal"],
        "lease_read_ratio": r["lease_reads"]["ratio"],
        "lease_reads_not_slower": r["lease_reads"]["ratio"] <= 1.0,
    }
    out["ok"] = bool(out["n_schedules"] >= CHAOS_SEEDS
                     and out["all_schedules_ok"]
                     and out["lin_violations"] == 0
                     and out["lost_acked_writes"] == 0
                     and out["failover_within_bound"]
                     and out["old_leader_fenced"]
                     and out["stalls_without_lease"]
                     and out["lease_reads_not_slower"])
    return out


def run_watchdog(quick: bool) -> dict:
    """--scenario watchdog (PR 9): the consensus-invariant watchdog gate.

    Three legs: (1) zero false positives — the watchdog must stay silent
    across the seeded gray-failure chaos schedules; (2) the mutation
    corpus — each known-fixed protocol bug re-introduced behind its
    test-only switch must be pinpointed at the violating transition,
    with the fixed control run silent; (3) bit-identity — a journaled +
    watchdog-monitored run must be op-for-op identical to one with the
    flight recorder off (observability is pure measurement)."""
    from repro.chaos.mutations import run_corpus

    seeds = range(2 if quick else CHAOS_SEEDS)
    duration = 8.0 if quick else 12.0
    silence = []
    for seed in seeds:
        print(f"watchdog: chaos schedule seed={seed} ...", flush=True)
        r = run_spinnaker_chaos(seed=seed, duration=duration)
        wd = r["watchdog"]
        print(f"  {'silent' if wd['ok'] else 'VIOLATIONS'}: "
              f"{wd['entries_checked']} journal entries checked, "
              f"{wd['n_violations']} violation(s)", flush=True)
        silence.append({"seed": seed, "ok": wd["ok"],
                        "entries_checked": wd["entries_checked"],
                        "n_violations": wd["n_violations"],
                        "by_invariant": wd["by_invariant"],
                        "violations": wd["violations"][:5]})

    print("watchdog: mutation corpus (3 known-fixed bugs, both arms) ...",
          flush=True)
    corpus = run_corpus()
    for name, m in corpus["mutations"].items():
        at = m["detected_at"]
        print(f"  {name}: detected={m['detected']}"
              + (f" at {at['kind']} t={at['t']:.3f}s" if at else "")
              + f", control_silent={m['control_silent']}", flush=True)

    print("watchdog: bit-identity, journaled vs un-journaled ...", flush=True)
    spec = WorkloadSpec(num_keys=500, key_dist="zipfian", zipf_theta=0.99,
                        read_frac=0.5, write_frac=0.5, rmw_frac=0.0,
                        cond_frac=0.0, value_size=1024)
    cfg = ExperimentConfig(n_nodes=5, disk="ssd", seed=11, n_clients=8,
                           warmup=0.5, duration=3.0, preload_cap=300)
    on = run_spinnaker_workload(spec, cfg, consistent_reads=True)
    cfg_off = dataclasses.replace(cfg, journal=False)
    off = run_spinnaker_workload(spec, cfg_off, consistent_reads=True)
    bit_identical = bool(
        on["total_ops"] == off["total_ops"]
        and on["writes"]["count"] == off["writes"]["count"]
        and on["reads"]["count"] == off["reads"]["count"]
        and on["writes"]["p50_ms"] == off["writes"]["p50_ms"]
        and on["writes"]["p99_ms"] == off["writes"]["p99_ms"]
        and on["reads"]["p50_ms"] == off["reads"]["p50_ms"]
        and on["reads"]["p99_ms"] == off["reads"]["p99_ms"])
    print(f"  bit_identical={bit_identical} "
          f"({on['total_ops']} ops each way)", flush=True)

    out = {"silence": silence, "corpus": corpus,
           "bit_identity": {"ok": bit_identical,
                            "total_ops": on["total_ops"],
                            "write_p50_ms": on["writes"]["p50_ms"],
                            "read_p50_ms": on["reads"]["p50_ms"]}}
    out["check"] = check_watchdog(out)
    print(f"  {out['check']}", flush=True)
    return out


def check_watchdog(r: dict) -> dict:
    """Acceptance surface: every chaos schedule watchdog-silent with a
    non-trivial number of entries checked, every mutation detected at
    the expected transition with its control arm silent, and the
    journaled run bit-identical to the un-journaled one."""
    silence = r["silence"]
    corpus = r["corpus"]["mutations"]
    out = {
        "n_schedules": len(silence),
        "all_silent": all(s["ok"] for s in silence),
        "entries_checked": sum(s["entries_checked"] for s in silence),
        "false_positives": sum(s["n_violations"] for s in silence),
        "mutations_detected": {n: m["detected"] for n, m in corpus.items()},
        "controls_silent": {n: m["control_silent"]
                            for n, m in corpus.items()},
        "bit_identical": r["bit_identity"]["ok"],
    }
    out["ok"] = bool(out["all_silent"]
                     and out["entries_checked"] > 10_000
                     and all(out["mutations_detected"].values())
                     and all(out["controls_silent"].values())
                     and len(corpus) >= 3
                     and out["bit_identical"])
    return out


def breakdown_spec(quick: bool) -> WorkloadSpec:
    """Plain read/write mix — no rmw/cond legs, so the 'write' trace
    population is exactly the strong-write path the report decomposes."""
    return WorkloadSpec(
        num_keys=1000 if quick else 3000,
        key_dist="zipfian", zipf_theta=0.99,
        read_frac=0.80, write_frac=0.20, rmw_frac=0.0, cond_frac=0.0,
        value_size=4096)


def breakdown_cfg(quick: bool) -> ExperimentConfig:
    return ExperimentConfig(
        n_nodes=5, disk="ssd", seed=4,
        n_clients=8 if quick else 16,
        warmup=0.5, duration=3.0 if quick else 10.0,
        preload_cap=1000, trace_sample=1.0, metrics_interval=0.25)


def _print_stage_table(name: str, b: dict) -> None:
    print(f"  {name}: write p50 {b['p50_ms']:.3f}ms p99 {b['p99_ms']:.3f}ms "
          f"({b['n_traces']} traces, stage sum {b['stage_sum_p50_ms']:.3f}ms)",
          flush=True)
    total = max(b["stage_sum_p50_ms"], 1e-12)
    for stage, ms in b["stages_p50_ms"].items():
        bar = "#" * int(round(40 * ms / total))
        print(f"    {stage:<12} {ms:8.4f} ms {100 * ms / total:5.1f}%  {bar}",
              flush=True)


def run_breakdown(quick: bool) -> dict:
    spec, cfg = breakdown_spec(quick), breakdown_cfg(quick)
    print("breakdown: spinnaker strong-write stage decomposition ...",
          flush=True)
    sp = run_spinnaker_breakdown(spec, cfg)
    _print_stage_table("spinnaker", sp)
    print("breakdown: cassandra quorum-write stage decomposition ...",
          flush=True)
    ca = run_cassandra_breakdown(spec, cfg)
    _print_stage_table("cassandra", ca)

    # Tracing overhead: the same run with sampling off.  Tracing models
    # zero sim-time, so the <5% throughput gate should hold exactly (the
    # two runs are bit-identical), not merely within noise.
    cfg_off = dataclasses.replace(cfg, trace_sample=0.0,
                                  metrics_interval=0.0)
    off = run_spinnaker_breakdown(spec, cfg_off)
    overhead = {"write_tput_traced": sp["write_throughput"],
                "write_tput_untraced": off["write_throughput"],
                "ratio": sp["write_throughput"]
                / max(off["write_throughput"], 1e-9)}

    # Trace-completeness invariants under the two nastiest schedules:
    # fig9's leader kill (write chains must close across failover) and
    # the mid-2PC coordinator kill (committed txn chains must close
    # through presumed-abort recovery).
    print("breakdown: completeness audit under leader kill ...", flush=True)
    fcfg = dataclasses.replace(cfg, seed=5, duration=6.0 if quick else 12.0,
                               metrics_interval=0.0, window=0.5)
    sched = LEADER_KILL.format(t_kill=1.5, t_back=fcfg.duration * 0.7)
    f9 = run_spinnaker_workload(spec, fcfg, consistent_reads=True,
                                schedule=sched)
    print(f"  write audit: {f9['trace_audit']}", flush=True)
    print("breakdown: completeness audit under mid-2PC coordinator kill ...",
          flush=True)
    tspec, tcfg = txn_spec(quick), txn_cfg(quick)
    d = tcfg.duration
    tsched = (f"at {d * 0.3:.2f}s crash txn coordinator\n"
              f"at {d * 0.75:.2f}s restart crashed")
    tk = run_spinnaker_txn(tspec, tcfg, cross_frac=0.5, schedule=tsched)
    print(f"  txn audit: {tk['txn']['trace_audit']}", flush=True)
    invariants = {
        "leader_kill_write_audit": f9["trace_audit"],
        "leader_kill_events": f9.get("cluster_events", [])[:50],
        "coord_kill_write_audit": tk["trace_audit"],
        "coord_kill_txn_audit": tk["txn"]["trace_audit"],
    }
    out = {"spinnaker": sp, "cassandra": ca,
           "tracing_overhead": overhead, "invariants": invariants}
    out["check"] = check_breakdown(out)
    print(f"  {out['check']}", flush=True)
    return out


def check_breakdown(r: dict) -> dict:
    """Acceptance surface: per-system stage contributions must sum to
    within 5% of the measured e2e write p50 (i.e. the stages really
    partition the path), every acked write/txn must carry a complete
    trace chain even across leader and coordinator kills, and tracing at
    full sampling must cost < 5% write throughput (expected: exactly 0,
    since spans record sim-time without consuming it)."""
    def sum_err(b: dict) -> float:
        return abs(b["stage_sum_p50_ms"] - b["p50_ms"]) \
            / max(b["p50_ms"], 1e-9)
    inv = r["invariants"]
    out = {
        "spinnaker_stage_sum_rel_err": sum_err(r["spinnaker"]),
        "cassandra_stage_sum_rel_err": sum_err(r["cassandra"]),
        "steady_audit_ok": bool(r["spinnaker"]["trace_audit"]["ok"]
                                and r["cassandra"]["trace_audit"]["ok"]),
        "leader_kill_audit_ok": bool(inv["leader_kill_write_audit"]["ok"]),
        "coord_kill_audit_ok": bool(inv["coord_kill_write_audit"]["ok"]
                                    and inv["coord_kill_txn_audit"]["ok"]),
        "tracing_overhead_ratio": r["tracing_overhead"]["ratio"],
        "overhead_ok": bool(r["tracing_overhead"]["ratio"] >= 0.95),
    }
    out["ok"] = bool(out["spinnaker_stage_sum_rel_err"] <= 0.05
                     and out["cassandra_stage_sum_rel_err"] <= 0.05
                     and out["steady_audit_ok"]
                     and out["leader_kill_audit_ok"]
                     and out["coord_kill_audit_ok"]
                     and out["overhead_ok"])
    return out


def profile_spec() -> WorkloadSpec:
    """Fixed 80/20 zipfian mix for the profile scenario — deliberately
    independent of --quick so the committed section and fresh smoke runs
    compare like for like in perf_diff.py."""
    return WorkloadSpec(num_keys=1000, key_dist="zipfian", zipf_theta=0.99,
                        read_frac=0.80, write_frac=0.20, rmw_frac=0.0,
                        cond_frac=0.0, value_size=1024)


def profile_cfg() -> ExperimentConfig:
    return ExperimentConfig(n_nodes=5, disk="ssd", seed=7, n_clients=8,
                            warmup=0.5, duration=3.0, preload_cap=500,
                            metrics_interval=0.25, profile_interval=0.25)


def _print_profile_summary(name: str, r: dict) -> None:
    prof = r["profile"]
    shares = prof.get("cpu_share_by_component", {})
    top = sorted(shares.items(), key=lambda kv: -kv[1])[:5]
    share_txt = "  ".join(f"{c}={100 * v:.1f}%" for c, v in top)
    print(f"  {name}: cluster cpu busy "
          f"{prof['cluster_cpu_busy_s'] * 1e3:.1f}ms over "
          f"{prof['elapsed_s']:.1f}s; top shares: {share_txt}", flush=True)


def run_profile(quick: bool) -> dict:
    """--scenario profile: component-attributed utilization for Spinnaker
    vs the Cassandra-eventual baseline at matched load, plus the two
    profiler invariants (attribution sums to measured busy time; a
    profiled run is bit-identical to an unprofiled one)."""
    spec, cfg = profile_spec(), profile_cfg()
    print("profile: spinnaker component-attributed utilization ...",
          flush=True)
    sp = run_spinnaker_profiled(spec, cfg, consistent_reads=True)
    _print_profile_summary("spinnaker", sp)
    print("profile: cassandra eventual at matched load ...", flush=True)
    ce = run_cassandra_profiled(spec, cfg, quorum=False)
    _print_profile_summary("cassandra_eventual", ce)

    # The profiler models zero sim-time and draws no RNG, so the same
    # run with all profiler/metrics accounting off must be bit-identical
    # (op-for-op equal populations and latencies), not merely close.
    print("profile: bit-identity control run (profiler off) ...", flush=True)
    cfg_off = dataclasses.replace(cfg, profile=False, profile_interval=0.0,
                                  metrics_interval=0.0)
    off = run_spinnaker_profiled(spec, cfg_off, consistent_reads=True)
    bit_identical = bool(
        sp["total_ops"] == off["total_ops"]
        and sp["writes"]["count"] == off["writes"]["count"]
        and sp["reads"]["count"] == off["reads"]["count"]
        and sp["writes"]["p50_ms"] == off["writes"]["p50_ms"]
        and sp["writes"]["p99_ms"] == off["writes"]["p99_ms"]
        and sp["reads"]["p50_ms"] == off["reads"]["p50_ms"]
        and sp["reads"]["p99_ms"] == off["reads"]["p99_ms"])

    out = {
        "spinnaker": sp,
        "cassandra_eventual": ce,
        # the ratcheting write-gap metric (paper §1: '5% to 10% slower')
        "write_p50_ratio": sp["writes"]["p50_ms"]
        / max(ce["writes"]["p50_ms"], 1e-9),
        "bit_identical": bit_identical,
    }
    out["check"] = check_profile(out)
    print(f"  write p50 ratio spinnaker/eventual = "
          f"{out['write_p50_ratio']:.2f}", flush=True)
    print(f"  {out['check']}", flush=True)
    return out


def check_profile(r: dict) -> dict:
    """Acceptance surface: per-node per-component busy-time attribution
    sums to the measured FifoServer/Disk busy time within 5% (i.e. the
    component labels really partition the capacity), and the profiled
    run is bit-identical to the unprofiled one."""
    worst = 0.0
    for system in ("spinnaker", "cassandra_eventual"):
        for _nid, nb in r[system]["profile"]["nodes"].items():
            for kind in ("cpu", "disk"):
                busy = nb[f"{kind}_busy_s"]
                if busy > 1e-9:
                    worst = max(worst, abs(nb[f"{kind}_attributed_s"] - busy)
                                / busy)
    out = {
        "max_attribution_rel_err": worst,
        "attribution_ok": bool(worst <= 0.05),
        "bit_identical": bool(r["bit_identical"]),
        "write_p50_ratio": r["write_p50_ratio"],
    }
    out["ok"] = bool(out["attribution_ok"] and out["bit_identical"])
    return out


def _print_trace_journal(t: dict) -> None:
    """One indented line per notable protocol-journal entry implicated
    in a slow trace's lifetime (regime changes, catch-up, crashes)."""
    jw = t.get("journal")
    if not jw:
        return
    for e in jw.get("notable", []):
        extra = e.get("why") or e.get("winner")
        print(f"      journal rid={t.get('rid')}: t={e['t']:.3f}s "
              f"{e['kind']} node={e['node']}"
              + (f" ({extra})" if extra is not None else ""))


def _print_txn_chains(chains: list[dict]) -> None:
    """Slowest 2PC transactions, keyed by txid, with their milestone
    chains and the txid's own journal entries."""
    for c in chains:
        print(f"  {c['txid']}: {c['outcome']} e2e={c['e2e_ms']:.3f}ms "
              f"coord=r{c['coordinator']} participants="
              f"{c['participants']}")
        print(f"      prepare_sent={c['prepare_sent_ms']} "
              f"vote={c['vote_ms']} decide={c['decide_ms']}ms "
              f"resolve={c['resolve_ms']} ack={c['client_ack_ms']}ms")
        for e in c.get("journal", [])[:12]:
            print(f"      journal: t={e['t']:.3f}s {e['kind']} "
                  f"node={e['node']} rid={e.get('rid')}"
                  + (f" {e.get('outcome')}" if e.get("outcome") else ""))


def print_report(path: str) -> int:
    """--report: pretty-print the committed breakdown block — per-stage
    write-p50 decomposition for both systems, the ten slowest traces
    with their implicated journal windows, the slowest txid-keyed 2PC
    chains, and the watchdog gate summary."""
    p = Path(path)
    if not p.exists():
        print(f"report: {path} not found")
        return 1
    rec = json.loads(p.read_text())
    bd = rec.get("breakdown")
    prof = rec.get("profile")
    txn = rec.get("txn")
    wd = rec.get("watchdog")
    if not bd and not prof and not txn and not wd:
        print(f"report: no 'breakdown' / 'profile' / 'txn' / 'watchdog' "
              f"block in {path}; run the matching --scenario first")
        return 1
    if bd:
        for name in ("spinnaker", "cassandra"):
            print(f"\n== {name}: write-path latency breakdown ==")
            _print_stage_table(name, bd[name])
        ov = bd.get("tracing_overhead", {})
        if ov:
            print(f"\ntracing overhead: traced "
                  f"{ov['write_tput_traced']:.0f}/s "
                  f"vs untraced {ov['write_tput_untraced']:.0f}/s "
                  f"(ratio {ov['ratio']:.3f})")
        print("\n== top 10 slowest spinnaker writes ==")
        for t in bd["spinnaker"].get("top_slowest", []):
            stages = t.get("stages_ms", {})
            worst = max(stages, key=stages.get) if stages else "?"
            print(f"  {t['trace_id']:<10} key={t['key']} node={t['node']} "
                  f"attempts={t['attempts']} e2e={t['e2e_ms']:.3f}ms "
                  f"dominant={worst} ({stages.get(worst, 0.0):.3f}ms)")
            _print_trace_journal(t)
        ck = bd.get("check", {})
        if ck:
            print(f"\ncheck: {'ok' if ck.get('ok') else 'FAIL'} "
                  f"(stage-sum rel err: spinnaker "
                  f"{ck['spinnaker_stage_sum_rel_err']:.4f}, cassandra "
                  f"{ck['cassandra_stage_sum_rel_err']:.4f}; overhead ratio "
                  f"{ck['tracing_overhead_ratio']:.3f})")
    if prof:
        for name in ("spinnaker", "cassandra_eventual"):
            if name not in prof:
                continue
            print(f"\n== {name}: component-attributed resource profile ==")
            for line in format_profile_report(prof[name]["profile"]):
                print(line)
        ck = prof.get("check", {})
        if ck:
            print(f"\nprofile check: {'ok' if ck.get('ok') else 'FAIL'} "
                  f"(max attribution rel err "
                  f"{ck['max_attribution_rel_err']:.4f}, bit_identical="
                  f"{ck['bit_identical']}, write p50 ratio "
                  f"{ck['write_p50_ratio']:.2f})")
    if txn:
        chains = (txn.get("kill", {}).get("txn", {})
                  .get("slow_txn_chains")
                  or txn.get("cross", {}).get("txn", {})
                  .get("slow_txn_chains"))
        if chains:
            print("\n== slowest 2PC transactions (txid-keyed chains, "
                  "ms from txn start) ==")
            _print_txn_chains(chains)
    if wd:
        ck = wd.get("check", {})
        print("\n== invariant watchdog ==")
        print(f"  {'ok' if ck.get('ok') else 'FAIL'}: "
              f"{ck.get('n_schedules')} chaos schedules "
              f"(all_silent={ck.get('all_silent')}, "
              f"{ck.get('entries_checked')} journal entries checked, "
              f"{ck.get('false_positives')} false positives); "
              f"bit_identical={ck.get('bit_identical')}")
        for name, det in (ck.get("mutations_detected") or {}).items():
            at = next((m.get("detected_at") for n, m in
                       wd.get("corpus", {}).get("mutations", {}).items()
                       if n == name), None)
            print(f"  mutation {name}: detected={det}"
                  + (f" at {at['kind']} t={at['t']:.3f}s "
                     f"[{at['invariant']}]" if at else ""))
    return 0


def run_failover(quick: bool, consistent_reads: bool) -> dict:
    cfg = base_cfg(quick, seed=1)
    cfg.duration = 8.0 if quick else 30.0
    cfg.window = 0.5
    t_kill = 2.0 if quick else 8.0
    t_back = cfg.duration * 0.75
    spec = base_spec(quick)
    sched = LEADER_KILL.format(t_kill=t_kill, t_back=t_back)
    r = run_spinnaker_workload(spec, cfg, consistent_reads=consistent_reads,
                               monotonic=not consistent_reads,
                               schedule=sched)
    r["t_kill"] = t_kill
    r["t_restart"] = t_back
    return r


def check_writes_resume(fig9: dict) -> dict:
    """Writes must come back after the leader kill with nobody touching
    the cluster (§6: a follower takes over within the session timeout)."""
    t_kill = fig9["t_kill"]
    post = [w for w in fig9["timeline"]["write"] if w["t_start"] > t_kill]
    resumed = [w for w in post if w["throughput"] > 0]
    # recovery time = first window after the kill with successful writes
    recovery_s = (resumed[0]["t_start"] - t_kill) if resumed else None
    ok = bool(resumed) and max(w["throughput"] for w in resumed) > 0
    return {"writes_resumed": ok,
            "recovery_window_start_s_after_kill": recovery_s,
            "post_kill_peak_write_tput": max(
                (w["throughput"] for w in post), default=0.0)}


# Paper-claim acceptance envelope (§1/§9 headlines, with reproduction
# slack): strong reads at or under quorum-read latency, writes within
# 30% of eventual-consistency writes, throughput within 5%.
CLAIM_TARGETS = {"read_vs_quorum_ratio_max": 1.05,
                 "write_p50_ratio_max": 1.30,
                 "throughput_ratio_min": 0.95}


def check_paper_claims(fig8: dict) -> dict:
    """Structured claim ratios from the fig8 arms.  `perf_diff.py` and
    smoke.sh ratchet these: the write/read gaps may only shrink and the
    throughput ratio may only grow across PRs (5% tolerance)."""
    sp, ce = fig8["spinnaker_strong"], fig8["cassandra_eventual"]
    cq = fig8["cassandra_quorum"]
    r_ratio = sp["reads"]["p50_ms"] / max(cq["reads"]["p50_ms"], 1e-9)
    w_ratio = sp["writes"]["p50_ms"] / max(ce["writes"]["p50_ms"], 1e-9)
    t_ratio = sp["throughput"] / max(ce["throughput"], 1e-9)
    tg = CLAIM_TARGETS
    return {
        "read_vs_quorum_ratio": r_ratio,
        "write_p50_ratio": w_ratio,
        "throughput_ratio": t_ratio,
        "targets": dict(tg),
        "ok": bool(r_ratio <= tg["read_vs_quorum_ratio_max"]
                   and w_ratio <= tg["write_p50_ratio_max"]
                   and t_ratio >= tg["throughput_ratio_min"]),
        "notes": [
            f"strong reads vs quorum reads p50 ratio = {r_ratio:.2f} "
            f"(paper: 'as fast or even faster', expect <= ~1.0)",
            f"spinnaker writes vs eventual writes p50 ratio = {w_ratio:.2f} "
            f"(paper: '5% to 10% slower', expect ~1.05-1.10)",
            f"throughput ratio spinnaker/eventual = {t_ratio:.2f}",
        ],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="all",
                    choices=["fig8", "fig9", "fig10", "saturation",
                             "rebalance", "txn", "breakdown", "profile",
                             "chaos", "watchdog", "figs8-10", "all",
                             "regress"])
    ap.add_argument("--quick", action="store_true",
                    help="short runs (CI / smoke mode)")
    ap.add_argument("--out", default="BENCH_spinnaker.json")
    ap.add_argument("--report", action="store_true",
                    help="pretty-print the breakdown block of --out "
                         "(stage table + slowest traces) and exit")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.report:
        return print_report(args.out)
    if args.scenario == "regress":
        return run_regression_gate(args.out)

    rec: dict = {"scenario": args.scenario, "quick": args.quick}
    if args.scenario in ("fig8", "figs8-10", "all"):
        rec["fig8"] = run_fig8(args.quick)
        rec["claims"] = check_paper_claims(rec["fig8"])
    if args.scenario in ("fig9", "figs8-10", "all"):
        print("fig9: leader kill under write load ...", flush=True)
        rec["fig9"] = run_failover(args.quick, consistent_reads=True)
        rec["fig9_check"] = check_writes_resume(rec["fig9"])
        print(f"  {rec['fig9_check']}", flush=True)
    if args.scenario in ("fig10", "figs8-10", "all"):
        print("fig10: leader kill under timeline reads ...", flush=True)
        rec["fig10"] = run_failover(args.quick, consistent_reads=False)
    if args.scenario in ("saturation", "all"):
        rec["saturation"] = run_saturation(args.quick)
    if args.scenario in ("rebalance", "all"):
        rec["rebalance"] = run_rebalance(args.quick)
        rec["rebalance_check"] = check_rebalance(rec["rebalance"])
        print(f"  {rec['rebalance_check']}", flush=True)
    if args.scenario in ("txn", "all"):
        rec["txn"] = run_txn(args.quick)
        rec["txn_check"] = check_txn(rec["txn"])
        print(f"  {rec['txn_check']}", flush=True)
    if args.scenario in ("breakdown", "all"):
        rec["breakdown"] = run_breakdown(args.quick)
    if args.scenario in ("profile", "all"):
        rec["profile"] = run_profile(args.quick)
    if args.scenario in ("chaos", "all"):
        rec["chaos"] = run_chaos(args.quick)
        rec["chaos"]["check"] = check_chaos(rec["chaos"])
        print(f"  {rec['chaos']['check']}", flush=True)
    if args.scenario in ("watchdog", "all"):
        rec["watchdog"] = run_watchdog(args.quick)

    # merge into an existing artifact instead of clobbering it: a single-
    # scenario run refreshes its own section and leaves the rest intact
    out_path = Path(args.out)
    if args.scenario != "all" and out_path.exists():
        try:
            merged = json.loads(out_path.read_text())
        except json.JSONDecodeError:
            merged = {}
        merged.update(rec)
        rec = merged
    out_path.write_text(json.dumps(rec, indent=2))
    print(f"wrote {args.out}")
    claims = rec.get("claims") or {}
    # pre-PR-10 artifacts stored claims as a bare list of strings
    for c in claims.get("notes", []) if isinstance(claims, dict) else claims:
        print("claim:", c)
    rc = 0
    if isinstance(claims, dict) and "fig8" in rec and not claims["ok"]:
        print(f"FAIL: paper-claim envelope missed: "
              f"read {claims['read_vs_quorum_ratio']:.2f} "
              f"write {claims['write_p50_ratio']:.2f} "
              f"tput {claims['throughput_ratio']:.2f} "
              f"vs targets {claims['targets']}")
        rc = 1
    if "fig9_check" in rec and not rec["fig9_check"]["writes_resumed"]:
        print("FAIL: writes did not resume after leader crash")
        rc = 1
    for disk, curves in rec.get("saturation", {}).items():
        if not curves["check"]["ok"]:
            print(f"FAIL: {disk} saturation check (>=25% peak gain, <=10% "
                  "light-load p50 cost) did not hold")
            rc = 1
        if not curves["check"].get("tail_ok", True):
            print(f"FAIL: {disk} overload tail collapsed below 60% of the "
                  "knee (retry backoff regression)")
            rc = 1
    if "rebalance_check" in rec and not rec["rebalance_check"]["ok"]:
        print("FAIL: rebalance scenario gate "
              f"{rec['rebalance_check']}")
        rc = 1
    if "txn_check" in rec and not rec["txn_check"]["ok"]:
        print("FAIL: cross-range transaction gate "
              f"{rec['txn_check']}")
        rc = 1
    if "breakdown" in rec and not rec["breakdown"]["check"]["ok"]:
        print("FAIL: latency-breakdown gate "
              f"{rec['breakdown']['check']}")
        rc = 1
    if "profile" in rec and not rec["profile"]["check"]["ok"]:
        print("FAIL: resource-profile gate "
              f"{rec['profile']['check']}")
        rc = 1
    if "chaos" in rec and not rec["chaos"]["check"]["ok"]:
        print("FAIL: chaos gate "
              f"{rec['chaos']['check']}")
        rc = 1
    if "watchdog" in rec and not rec["watchdog"]["check"]["ok"]:
        print("FAIL: invariant-watchdog gate "
              f"{rec['watchdog']['check']}")
        rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
