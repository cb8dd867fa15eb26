import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × shape × mesh) cell:
  1. lower + compile the FULL config under GSPMD on the production mesh —
     this is the runnability proof, and memory_analysis() is exact
     (buffer assignment accounts for loop reuse);
  2. lower + compile two reduced-DEPTH configs (L1 = one layer period,
     L2 = two periods) with layers UNROLLED, because XLA's cost analysis
     counts a while-loop body exactly once — per-layer flops / bytes /
     collective traffic are the (L2 − L1) delta, extrapolated to L exactly
     (scanned layers are identical by construction);
  3. derive the three roofline terms and write one JSON per cell
     (resumable).

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The XLA_FLAGS line above MUST stay before any jax import: jax locks the
device count at first backend init.  Only this entry point forces 512
host devices; tests and benches see the real device.
"""

import argparse
import json
import time
import traceback
from pathlib import Path


def _analysis_depths(cfg) -> tuple[int, int, int]:
    """(L1, L2, period): delta of one full period captures the repeating
    unit (hybrid: attn_every mamba blocks + one shared-attention slot)."""
    period = cfg.attn_every if cfg.family == "hybrid" and cfg.attn_every \
        else 1
    return period, 2 * period, period


def _lower(cfg, shape: str, mesh, pol, weight_quant: bool = False):
    """Lower + compile one step for `cfg`; returns (compiled, lower_s,
    compile_s)."""
    import jax

    from ..dist.sharding import MeshContext
    from ..models import init_params
    from ..train.optim import choose_optimizer
    from ..train.step import (TrainConfig, init_train_state,
                              make_prefill_step, make_serve_step,
                              make_train_step)
    from .shapes import SHAPES, input_specs

    spec = SHAPES[shape]
    t0 = time.time()
    with MeshContext(mesh, cfg, pol) as ctx:
        if spec.kind == "train":
            tcfg = TrainConfig(optimizer=choose_optimizer(cfg.param_count()))
            step = make_train_step(cfg, tcfg)
            state_shape = jax.eval_shape(
                lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
            state_shard = {
                "params": ctx.param_shardings(state_shape["params"]),
                "opt": _opt_shardings(ctx, state_shape["opt"]),
                "step": ctx.replicated(),
            }
            batch = input_specs(cfg, shape)
            jitted = jax.jit(step,
                             in_shardings=(state_shard,
                                           ctx.batch_sharding(batch)),
                             out_shardings=(state_shard, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_shape, batch)
        elif spec.kind == "prefill":
            step = make_prefill_step(cfg)
            params_shape = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), cfg))
            batch = input_specs(cfg, shape)
            jitted = jax.jit(step,
                             in_shardings=(ctx.param_shardings(params_shape),
                                           ctx.batch_sharding(batch)))
            lowered = jitted.lower(params_shape, batch)
        else:  # decode
            step = make_serve_step(cfg)
            if weight_quant:
                from ..models.quant import quantize_tree
                params_shape = jax.eval_shape(
                    lambda: quantize_tree(
                        init_params(jax.random.PRNGKey(0), cfg)))
            else:
                params_shape = jax.eval_shape(
                    lambda: init_params(jax.random.PRNGKey(0), cfg))
            specs = input_specs(cfg, shape)
            cache_shape, tok = specs["cache"], specs["tokens"]
            cache_shard = ctx.cache_sharding(cache_shape)
            jitted = jax.jit(step,
                             in_shardings=(ctx.param_shardings(params_shape),
                                           cache_shard,
                                           ctx.batch_sharding(tok)),
                             out_shardings=(None, cache_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_shape, cache_shape, tok)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: Path,
             seq_parallel: bool = False, shard_params_on_pod=None,
             overwrite: bool = False, tag: str = "",
             attn_impl: str = None, moe_impl: str = None,
             weight_quant: bool = False, serve_stationary: bool = False,
             remat_off: bool = False, remat_policy: str = None,
             decode_attn_impl: str = None, skip_full: bool = False) -> dict:
    import jax

    from ..configs import get_config
    from ..dist.sharding import ShardingPolicy
    from . import hlo as hlo_mod
    from . import roofline as roof_mod
    from .mesh import make_production_mesh
    from .shapes import SHAPES, applicable

    cfg = get_config(arch)
    if attn_impl:
        cfg = cfg.scaled(attn_impl=attn_impl)
    if moe_impl:
        cfg = cfg.scaled(moe_impl=moe_impl)
    if remat_off:
        cfg = cfg.scaled(remat=False)
    if decode_attn_impl:
        cfg = cfg.scaled(decode_attn_impl=decode_attn_impl)
    if remat_policy:
        cfg = cfg.scaled(remat_policy=remat_policy)
    spec = SHAPES[shape]
    ok, reason = applicable(cfg, shape)
    cell_id = f"{arch}__{shape}__{mesh_name}" + (f"__{tag}" if tag else "")
    out_path = out_dir / f"{cell_id}.json"
    if out_path.exists() and not overwrite:
        return json.loads(out_path.read_text())
    if not ok:
        rec = {"cell": cell_id, "status": "skipped", "reason": reason}
        out_dir.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    multi_pod = mesh_name == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    if shard_params_on_pod is None:
        shard_params_on_pod = multi_pod and cfg.param_count() > 4e11
    pol = ShardingPolicy.for_mesh(mesh, seq_parallel=seq_parallel,
                                  shard_params_on_pod=shard_params_on_pod)
    if serve_stationary:
        # weight-stationary serving: params replicated over the data axes
        # (TP-only sharding); decode loses its per-step FSDP all-gathers
        pol.fsdp_axes = ()

    # --- 1. full-config compile: runnability proof + memory analysis -------
    mem = None
    full_collectives = None
    t_lower = t_compile = 0.0
    if not skip_full:
        compiled_full, t_lower, t_compile = _lower(cfg, shape, mesh, pol,
                                                   weight_quant)
        try:
            mem = compiled_full.memory_analysis()
        except Exception:
            mem = None
        full_collectives = hlo_mod.parse_collectives(
            compiled_full.as_text(), chips)
        del compiled_full

    # --- 2. depth-extrapolated cost analysis --------------------------------
    L1, L2, period = _analysis_depths(cfg)
    L = cfg.num_layers
    costs = []
    colls = []
    for depth in (L1, L2):
        cfg_a = cfg.scaled(num_layers=depth, scan_layers=False)
        compiled_a, _, _ = _lower(cfg_a, shape, mesh, pol, weight_quant)
        costs.append(compiled_a.cost_analysis() or {})
        colls.append(hlo_mod.parse_collectives(compiled_a.as_text(), chips))
        del compiled_a

    def extrap(v1: float, v2: float) -> float:
        return v1 + (v2 - v1) * (L - L1) / float(L2 - L1)

    flops = extrap(float(costs[0].get("flops", 0)),
                   float(costs[1].get("flops", 0)))
    byts = extrap(float(costs[0].get("bytes accessed", 0)),
                  float(costs[1].get("bytes accessed", 0)))
    link_bytes = extrap(colls[0].total_link_bytes, colls[1].total_link_bytes)

    roof = roof_mod.derive(arch, shape, mesh_name, chips,
                           {"flops": flops, "bytes accessed": byts}, mem,
                           link_bytes, cfg)

    per_layer_coll = {}
    for op in set(list(colls[0].counts) + list(colls[1].counts)):
        per_layer_coll[op] = {
            "count_per_period": colls[1].counts.get(op, 0)
            - colls[0].counts.get(op, 0),
            "link_bytes_per_period": colls[1].link_bytes.get(op, 0.0)
            - colls[0].link_bytes.get(op, 0.0),
        }

    rec = {
        "cell": cell_id,
        "status": "ok",
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "seq_parallel": seq_parallel,
        "shard_params_on_pod": shard_params_on_pod,
        "attn_impl": attn_impl or cfg.attn_impl,
        "moe_impl": moe_impl or cfg.moe_impl,
        "weight_quant": weight_quant,
        "serve_stationary": serve_stationary,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "analysis_depths": [L1, L2],
        "cost_extrapolated": {"flops": flops, "bytes_accessed": byts,
                              "link_bytes": link_bytes},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        } if mem else None,
        "collectives_per_period": per_layer_coll,
        "collectives_full_hlo_bodyonce": full_collectives.table()
        if full_collectives else None,
        "roofline": roof.to_dict(),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def _opt_shardings(ctx, opt_shape):
    """Optimizer state follows its parameter's sharding; scalars replicate.

    AdamW m/v mirror the param tree exactly; Adafactor factored stats drop
    the last (vr) or second-to-last (vc) axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..dist.sharding import _drop_indivisible, param_spec, path_str

    def one(path, leaf):
        ps = path_str(path)
        if leaf.ndim == 0 or ps.endswith("count"):
            return ctx.replicated()
        parts = [p for p in ps.split("/")
                 if p not in ("m", "v", "stats", "vr", "vc")]

        class _K:
            def __init__(self, k):
                self.key = k

        pseudo = tuple(_K(p) for p in parts)
        spec = param_spec(pseudo, leaf, ctx.pol, ctx.cfg)
        tail = ps.rsplit("/", 1)[-1]
        if tail == "vr":
            spec = P(*(list(spec)[:-1]))
        elif tail == "vc":
            s = list(spec)
            if len(s) >= 2:
                spec = P(*(s[:-2] + s[-1:]))
        spec = _drop_indivisible(spec, leaf, ctx.mesh)
        return NamedSharding(ctx.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, opt_shape)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="architecture id (see repro.configs)")
    ap.add_argument("--shape",
                    help="train_4k|prefill_32k|decode_32k|long_500k")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--overwrite", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--attn-impl", default=None,
                    choices=[None, "xla", "xla_chunked", "xla_bhsd"])
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "gspmd", "shard_map"])
    ap.add_argument("--weight-quant", action="store_true",
                    help="int8 weight-only serving quantization")
    ap.add_argument("--remat-off", action="store_true",
                    help="disable activation checkpointing")
    ap.add_argument("--remat-policy", default=None,
                    choices=[None, "full", "dots"])
    ap.add_argument("--decode-attn-impl", default=None,
                    choices=[None, "xla", "shard_map"])
    ap.add_argument("--serve-stationary", action="store_true",
                    help="replicate weights over data axes for decode")
    ap.add_argument("--skip-full", action="store_true",
                    help="skip the full-depth compile (analysis only)")
    args = ap.parse_args()

    from ..configs import list_archs
    from .shapes import SHAPES

    out_dir = Path(args.out)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                for mesh in meshes:
                    cells.append((arch, shape, mesh))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        for mesh in meshes:
            cells.append((args.arch, args.shape, mesh))

    failures = 0
    for arch, shape, mesh in cells:
        cid = f"{arch}__{shape}__{mesh}"
        try:
            t0 = time.time()
            rec = run_cell(arch, shape, mesh, out_dir,
                           seq_parallel=args.seq_parallel,
                           overwrite=args.overwrite, tag=args.tag,
                           attn_impl=args.attn_impl,
                           moe_impl=args.moe_impl,
                           weight_quant=args.weight_quant,
                           serve_stationary=args.serve_stationary,
                           remat_off=args.remat_off,
                           remat_policy=args.remat_policy,
                           decode_attn_impl=args.decode_attn_impl,
                           skip_full=args.skip_full)
            status = rec.get("status")
            if status == "ok":
                r = rec["roofline"]
                msg = (f"[OK ] {cid}: dominant={r['dominant']} "
                       f"mfu={r['mfu']:.3f} compile={rec['compile_s']}s "
                       f"({time.time()-t0:.0f}s)")
                if rec.get("memory") and rec["memory"]["argument_bytes"]:
                    per_dev = (rec["memory"]["argument_bytes"]
                               + (rec["memory"]["temp_bytes"] or 0))
                    msg += f" mem/dev={per_dev/1e9:.1f}GB"
                    if per_dev > 16e9:
                        msg += " (>16GB HBM!)"
                print(msg, flush=True)
            else:
                print(f"[SKIP] {cid}: {rec.get('reason')}", flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {cid}: {e}", flush=True)
            (out_dir / f"{cid}.error.txt").parent.mkdir(parents=True,
                                                        exist_ok=True)
            (out_dir / f"{cid}.error.txt").write_text(traceback.format_exc())
    print(f"done: {len(cells) - failures}/{len(cells)} cells ok", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
