#!/usr/bin/env bash
# Pre-merge gate: a short workload scenario against a 5-node cluster
# (leader kill included), a fast rebalance gate (a capped zipfian run with
# one forced live split must keep write availability >= 99% and end with
# >= 2 non-empty ranges), a fast txn gate (cross-range transfer mix with a
# mid-2PC coordinator kill: zero acknowledged-but-lost transactions, the
# balance sum must close, abort rate bounded), trace-completeness audits
# on both kill runs (every acked write / committed 2PC txn must carry a
# full span chain), a breakdown gate (the per-stage decomposition must
# partition the measured write p50 within 5%) with a schema check of the
# committed BENCH_spinnaker.json "breakdown" block, a chaos gate (two
# seeded gray-failure schedules with linearizability / availability /
# lost-write / trace audits all clean, plus the minority-partitioned-
# leader pair: lease-bounded failover vs stall-until-heal) with a schema
# check of the committed "chaos" block, a watchdog gate (the consensus-
# invariant watchdog must stay silent on seeded chaos schedules, detect
# every mutation-corpus bug at the violating transition with silent
# fixed-protocol controls, and journaling must be bit-identical to a
# journal-off run) with a schema check of the committed "watchdog"
# block, a profile gate (the component-
# attributed resource profiler must account for the measured busy time
# within 5% and be bit-identical to an unprofiled run) with a schema
# check of the committed "profile" block, the perf_diff.py ratchet (a
# fresh --scenario profile run must not slip the committed write-gap
# ratio or utilization shares), a perf-regression check against the
# committed BENCH_spinnaker.json (fig8 write throughput + a capped
# saturation quick-sweep must not regress >10% / lose the batching
# edge), plus the tier-1 test suite.
#
#     bash benchmarks/smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# a CPU gate: it stays off any chip (python chip_smoke.py is the chip run)
export JAX_PLATFORMS=cpu

echo "== workload smoke: 5s scenario on a 5-node cluster =="
python - <<'EOF'
from repro.workload import (ExperimentConfig, WorkloadSpec,
                            run_spinnaker_workload)

cfg = ExperimentConfig(n_nodes=5, disk="mem", n_clients=4,
                       warmup=0.5, duration=5.0, window=0.5, preload_cap=100)
spec = WorkloadSpec(num_keys=100, value_size=512,
                    read_frac=0.5, write_frac=0.5, rmw_frac=0, cond_frac=0)
r = run_spinnaker_workload(
    spec, cfg, schedule="at 1.0s crash leader of 0\nat 4.0s restart crashed")
post = [w for w in r["timeline"]["write"] if w["t_start"] > 1.0]
assert max(w["throughput"] for w in post) > 0, "writes never resumed"
assert r["reads"]["count"] > 0 and r["writes"]["count"] > 0
# trace-completeness invariant: every acked write must carry a full
# propose -> quorum-ack -> commit -> apply chain, even across the kill
ta = r["trace_audit"]
assert ta["ok"], ta
print(f"ok: {r['total_ops']} ops, reads p99={r['reads']['p99_ms']:.2f}ms, "
      f"writes resumed after leader kill, "
      f"{ta['acked_writes_traced']} traces complete")
EOF

echo "== rebalance gate: forced live split under capped zipfian load =="
python - <<'EOF'
import warnings
warnings.filterwarnings("ignore")
from repro.workload import (ExperimentConfig, WorkloadSpec,
                            run_spinnaker_rebalance)

spec = WorkloadSpec(num_keys=300, key_dist="zipfian", zipf_theta=0.99,
                    read_frac=0.2, write_frac=0.8, rmw_frac=0, cond_frac=0,
                    value_size=512)
cfg = ExperimentConfig(n_nodes=5, disk="mem", driver="open", open_rate=1000,
                       warmup=0.5, duration=5.0, window=0.5, preload_cap=200)
r = run_spinnaker_rebalance(spec, cfg, kill_leader=False)
rb = r["rebalance"]
assert not rb["lost_acked_writes"], rb["lost_acked_writes"]
assert rb["write_availability"] >= 0.99, rb["write_availability"]
assert rb["n_ranges_end"] >= rb["n_ranges_start"] + 1, rb["n_ranges_end"]
assert rb["all_ranges_serving_writes"], rb["serving"]
# >= 2 non-empty ranges: the split boundary has data on both sides
assert rb["non_empty_ranges"] >= 2, rb["non_empty_ranges"]
assert rb["acked_writes_ledgered"] > 0
print(f"ok: ranges {rb['n_ranges_start']} -> {rb['n_ranges_end']}, "
      f"write availability {rb['write_availability']:.4f}, "
      f"{rb['acked_writes_ledgered']} acked writes audited, 0 lost")
EOF

echo "== txn gate: cross-range transfers + mid-2PC coordinator kill =="
python - <<'EOF'
import warnings
warnings.filterwarnings("ignore")
from repro.workload import (ExperimentConfig, WorkloadSpec,
                            run_spinnaker_txn)

spec = WorkloadSpec(num_keys=300, key_dist="uniform",
                    read_frac=0.2, write_frac=0, rmw_frac=0, cond_frac=0,
                    txn_frac=0.8, value_size=64)
cfg = ExperimentConfig(n_nodes=5, disk="mem", n_clients=8,
                       warmup=0.5, duration=4.0, window=0.5, preload_cap=300)
r = run_spinnaker_txn(spec, cfg, cross_frac=0.5,
                      schedule="at 1.2s crash txn coordinator\n"
                               "at 3.0s restart crashed")
t = r["txn"]
assert any("crash node" in e for e in r["fault_events"]), r["fault_events"]
assert not t["lost_acked_txns"], t["lost_acked_txns"]
assert not t["partial_commit"], (t["balance_read"], t["balance_expected"])
assert not t["unresolved_intents"] and t["leftover_locks"] == 0
assert t["txn_abort_rate"] <= 0.25, t["txn_abort_rate"]
assert t["txn_commits"] > 0 and t["txn2_issued"] > 0
# every committed 2PC txn must show a full prepare -> vote -> decide ->
# resolve chain on every participant, through the coordinator kill
ta = t["trace_audit"]
assert ta["ok"], ta
print(f"ok: {t['acked_txns_ledgered']} acked transfers audited through a "
      f"mid-2PC coordinator kill, 0 lost, balance closed "
      f"({t['balance_read']}), abort rate {t['txn_abort_rate']:.3f}, "
      f"{ta['committed_txns']} txn traces complete")
EOF

echo "== breakdown gate: stage decomposition must partition the write p50 =="
python - <<'EOF'
import warnings
warnings.filterwarnings("ignore")
from repro.workload import (ExperimentConfig, WorkloadSpec,
                            run_spinnaker_breakdown)

spec = WorkloadSpec(num_keys=300, key_dist="zipfian", zipf_theta=0.99,
                    read_frac=0.5, write_frac=0.5, rmw_frac=0, cond_frac=0,
                    value_size=512)
cfg = ExperimentConfig(n_nodes=5, disk="mem", n_clients=4,
                       warmup=0.5, duration=3.0, preload_cap=200,
                       trace_sample=1.0, metrics_interval=0.25)
r = run_spinnaker_breakdown(spec, cfg)
assert r["trace_audit"]["ok"], r["trace_audit"]
err = abs(r["stage_sum_p50_ms"] - r["p50_ms"]) / r["p50_ms"]
assert err <= 0.05, (r["stage_sum_p50_ms"], r["p50_ms"])
assert r["metrics"], "metrics scrape produced nothing"
print(f"ok: {r['n_traces']} write traces, stage sum "
      f"{r['stage_sum_p50_ms']:.3f}ms vs p50 {r['p50_ms']:.3f}ms "
      f"(rel err {err:.4f}), {len(r['metrics'])} metric series")
EOF

echo "== breakdown schema check vs committed BENCH_spinnaker.json =="
python - <<'EOF'
import json, math, pathlib
p = pathlib.Path("BENCH_spinnaker.json")
if not p.exists():
    print("skip: no committed BENCH_spinnaker.json")
    raise SystemExit(0)
bd = json.loads(p.read_text()).get("breakdown")
assert bd, "committed BENCH_spinnaker.json lacks a 'breakdown' block"
for system in ("spinnaker", "cassandra"):
    b = bd[system]
    for key in ("n_traces", "p50_ms", "p99_ms", "stages_p50_ms",
                "stage_sum_p50_ms", "top_slowest", "trace_audit"):
        assert key in b, (system, key)
    assert b["n_traces"] > 0 and b["trace_audit"]["ok"], system
    assert math.isclose(b["stage_sum_p50_ms"],
                        sum(b["stages_p50_ms"].values()), rel_tol=1e-9)
    assert abs(b["stage_sum_p50_ms"] - b["p50_ms"]) <= 0.05 * b["p50_ms"]
assert bd["check"]["ok"], bd["check"]
print("ok: committed breakdown block well-formed, stage sums within 5% "
      "of p50 for both systems")
EOF

echo "== chaos gate: seeded gray-failure schedules + minority-leader lease =="
python - <<'EOF'
import warnings
warnings.filterwarnings("ignore")
from repro.workload import run_spinnaker_chaos, run_spinnaker_minority_leader

for seed in (0, 1):
    r = run_spinnaker_chaos(seed=seed, duration=8.0)
    assert r["linearizability"]["ok"], r["linearizability"]["violations"][:3]
    assert r["availability"]["ok"], r["availability"]["violations"][:3]
    assert not r["lost_acked_writes"], r["lost_acked_writes"][:3]
    assert r["trace_audit"]["ok"], r["trace_audit"]
    assert r["ok"]
    print(f"ok: seed={seed} {r['history_ops']} history ops under "
          f"{len(r['fault_events'])} faults, all audits clean")

on = run_spinnaker_minority_leader(lease_enabled=True)
bound = on["lease_duration_s"] + 1.0
assert on["failover_s"] is not None and on["failover_s"] <= bound, on
assert not on["old_leader_lease_valid"] and on["old_leader_role"] != "LEADER"
off = run_spinnaker_minority_leader(lease_enabled=False)
assert off["stalled_until_heal"], off
print(f"ok: minority-partitioned leader fails over in {on['failover_s']}s "
      f"(bound {bound}s) with leases; stalls until heal without")
EOF

echo "== chaos schema check vs committed BENCH_spinnaker.json =="
python - <<'EOF'
import json, pathlib
p = pathlib.Path("BENCH_spinnaker.json")
if not p.exists():
    print("skip: no committed BENCH_spinnaker.json")
    raise SystemExit(0)
ch = json.loads(p.read_text()).get("chaos")
assert ch, "committed BENCH_spinnaker.json lacks a 'chaos' block"
assert len(ch["runs"]) >= 8, len(ch["runs"])
for run in ch["runs"]:
    for key in ("seed", "schedule", "fault_events", "linearizability",
                "availability", "lost_acked_writes", "client_robustness",
                "trace_audit", "ok"):
        assert key in run, key
    assert run["ok"], (run["seed"], run["linearizability"],
                       run["availability"])
ml = ch["minority_leader"]
assert ml["lease_on"]["failover_s"] is not None
assert ml["lease_off"]["stalled_until_heal"]
ck = ch["check"]
assert ck["ok"], ck
print(f"ok: committed chaos block well-formed — {len(ch['runs'])} seeded "
      f"schedules all green, failover {ck['failover_s_with_lease']}s <= "
      f"{ck['failover_bound_s']}s, lease-read ratio "
      f"{ck['lease_read_ratio']:.2f}")
EOF

echo "== watchdog gate: invariant silence + mutation corpus + bit-identity =="
python benchmarks/spinnaker_bench.py --scenario watchdog --quick \
    --out /tmp/BENCH_watchdog_fresh.json

echo "== watchdog schema check vs committed BENCH_spinnaker.json =="
python - <<'EOF'
import json, pathlib
p = pathlib.Path("BENCH_spinnaker.json")
if not p.exists():
    print("skip: no committed BENCH_spinnaker.json")
    raise SystemExit(0)
wd = json.loads(p.read_text()).get("watchdog")
assert wd, "committed BENCH_spinnaker.json lacks a 'watchdog' block"
for key in ("silence", "corpus", "bit_identity", "check"):
    assert key in wd, key
# zero false positives across every committed seeded schedule
assert len(wd["silence"]) >= 8, len(wd["silence"])
for s in wd["silence"]:
    assert s["ok"] and s["n_violations"] == 0, s
    assert s["entries_checked"] > 10_000, s
# every mutation-corpus bug detected at the violating transition, with
# the fixed control arm silent
muts = wd["corpus"]["mutations"]
assert len(muts) >= 3, list(muts)
for name, m in muts.items():
    assert m["detected"], name
    assert m["detected_at"] is not None, name
    assert m["control_silent"], name
assert wd["bit_identity"]["ok"], wd["bit_identity"]
ck = wd["check"]
assert ck["ok"], ck
print(f"ok: committed watchdog block well-formed — "
      f"{len(wd['silence'])} schedules silent "
      f"({ck['entries_checked']} entries, 0 false positives), "
      f"{len(muts)} mutations detected with silent controls, "
      f"bit_identical={ck['bit_identical']}")
EOF

echo "== profile gate: component attribution + bit-identity =="
python benchmarks/spinnaker_bench.py --scenario profile --quick \
    --out /tmp/BENCH_profile_fresh.json

echo "== profile schema check vs committed BENCH_spinnaker.json =="
python - <<'EOF'
import json, pathlib
p = pathlib.Path("BENCH_spinnaker.json")
if not p.exists():
    print("skip: no committed BENCH_spinnaker.json")
    raise SystemExit(0)
pr = json.loads(p.read_text()).get("profile")
assert pr, "committed BENCH_spinnaker.json lacks a 'profile' block"
for system in ("spinnaker", "cassandra_eventual"):
    prof = pr[system]["profile"]
    for key in ("nodes", "cpu_share_by_component", "cluster_cpu_busy_s",
                "heat", "timeline", "elapsed_s"):
        assert key in prof, (system, key)
    assert prof["nodes"], system
    for nid, nb in prof["nodes"].items():
        for key in ("cpu_busy_s", "cpu_attributed_s", "cpu_by_component",
                    "disk_busy_s", "disk_attributed_s", "disk_by_component",
                    "net_msgs_by_component", "queue_wait_s_by_component"):
            assert key in nb, (system, nid, key)
    shares = prof["cpu_share_by_component"]
    assert shares and abs(sum(shares.values()) - 1.0) <= 0.05, shares
ck = pr["check"]
assert ck["ok"], ck
print(f"ok: committed profile block well-formed — attribution rel err "
      f"{ck['max_attribution_rel_err']:.4f}, bit_identical="
      f"{ck['bit_identical']}, write p50 ratio "
      f"{ck['write_p50_ratio']:.2f}")
EOF

echo "== claims + saturation-retention check vs committed BENCH =="
python - <<'EOF'
import json, pathlib
p = pathlib.Path("BENCH_spinnaker.json")
if not p.exists():
    print("skip: no committed BENCH_spinnaker.json")
    raise SystemExit(0)
rec = json.loads(p.read_text())
cl = rec.get("claims")
assert isinstance(cl, dict), "committed claims block is not structured"
for key in ("read_vs_quorum_ratio", "write_p50_ratio", "throughput_ratio",
            "targets", "ok"):
    assert key in cl, key
tg = cl["targets"]
assert cl["write_p50_ratio"] <= tg["write_p50_ratio_max"], cl
assert cl["throughput_ratio"] >= tg["throughput_ratio_min"], cl
assert cl["read_vs_quorum_ratio"] <= tg["read_vs_quorum_ratio_max"], cl
assert cl["ok"], cl
sat = rec.get("saturation", {})
assert sat, "committed BENCH_spinnaker.json lacks a 'saturation' block"
for disk, curves in sat.items():
    ck = curves["check"]
    assert ck.get("admission_enabled"), (disk, "admission off in bench")
    assert ck.get("retention_ok"), (disk, ck.get("post_knee_off"),
                                    ck.get("post_knee_adaptive"))
    for arm in ("post_knee_off", "post_knee_adaptive"):
        pk = ck[arm]
        assert pk["post_knee_retention"] >= 0.70, (disk, arm, pk)
print(f"ok: claims write {cl['write_p50_ratio']:.2f} <= "
      f"{tg['write_p50_ratio_max']}, tput {cl['throughput_ratio']:.2f} >= "
      f"{tg['throughput_ratio_min']}, read {cl['read_vs_quorum_ratio']:.2f}"
      f" <= {tg['read_vs_quorum_ratio_max']}; post-knee retention >= 0.70 "
      f"on {len(sat)} disk classes (admission on)")
EOF

echo "== perf_diff ratchet: fresh profile run vs committed baseline =="
python benchmarks/perf_diff.py BENCH_spinnaker.json BENCH_spinnaker.json
python benchmarks/perf_diff.py BENCH_spinnaker.json \
    /tmp/BENCH_profile_fresh.json

echo "== perf-regression gate vs committed BENCH_spinnaker.json =="
python benchmarks/spinnaker_bench.py --scenario regress --quick \
    --out BENCH_spinnaker.json

echo "== tier-1 suite =="
python -m pytest -x -q
