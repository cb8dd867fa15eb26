"""Token-choice top-k MoE with capacity-bounded, sort-based dispatch.

The dispatch avoids the GShard one-hot einsum (whose dispatch matmul FLOPs
would dwarf expert FLOPs at E=384): tokens are argsorted by expert id,
positioned within their expert's capacity, gathered into an (E, C, D)
buffer (pure data movement, zero matmul FLOPs), run through batched
per-expert GEMMs, and scatter-added back weighted by the router gate.
Overflow tokens are dropped (capacity_factor bounds the buffer), which is
the standard load-shedding behaviour at scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init, linear, mlp, init_mlp, pshard
from .quant import is_quantized, wcast


def init_moe(rng, cfg: ModelConfig, dtype):
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(rng, 5)
    params = {
        "router": dense_init(ks[0], (D, E), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (E, D, F), in_axis=1, dtype=dtype),
        "w_up": dense_init(ks[2], (E, D, F), in_axis=1, dtype=dtype),
        "w_down": dense_init(ks[3], (E, F, D), in_axis=1, dtype=dtype),
    }
    if cfg.shared_expert_d_ff:
        params["shared"] = init_mlp(ks[4], D, cfg.shared_expert_d_ff, dtype)
    return params


def _route(params, xf: jax.Array, cfg: ModelConfig):
    """Router top-k + Switch-style load-balancing aux.  xf: (T, D)."""
    E, K = cfg.num_experts, cfg.experts_per_token
    T = xf.shape[0]
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)          # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (T * K))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return gate_vals, expert_idx, aux


def _dispatch_tables(expert_idx, gate_vals, T: int, E: int, K: int, C: int):
    """Sort-based capacity dispatch: (E, C) token-id + gate buffers."""
    flat_e = expert_idx.reshape(-1)                          # (T*K,)
    order = jnp.argsort(flat_e, stable=True)                 # slots by expert
    sorted_e = flat_e[order]
    sorted_tok = order // K
    sorted_gate = gate_vals.reshape(-1)[order]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(T * K) - group_start[sorted_e]
    keep = pos_in_e < C

    buf = jnp.full((E, C), T, dtype=jnp.int32)               # T = pad id
    buf = buf.at[jnp.where(keep, sorted_e, E - 1),
                 jnp.where(keep, pos_in_e, C - 1)].set(
        jnp.where(keep, sorted_tok, T).astype(jnp.int32), mode="drop")
    gbuf = jnp.zeros((E, C), jnp.float32)
    gbuf = gbuf.at[jnp.where(keep, sorted_e, E - 1),
                   jnp.where(keep, pos_in_e, C - 1)].set(
        jnp.where(keep, sorted_gate, 0.0), mode="drop")
    return buf, gbuf


def moe_ffn(params, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, D) -> (y, aux_loss).  Dispatch impl per cfg.moe_impl."""
    if cfg.moe_impl == "shard_map":
        from ..dist.context import current_ctx
        ctx = current_ctx()
        if ctx is not None:
            return _moe_shard_map(params, x, cfg, ctx)
    return _moe_gspmd(params, x, cfg)


def _moe_gspmd(params, x: jax.Array, cfg: ModelConfig):
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xf = x.reshape(T, D)
    gate_vals, expert_idx, aux = _route(params, xf, cfg)
    C = max(1, int(cfg.capacity_factor * T * K / E))
    buf, gbuf = _dispatch_tables(expert_idx, gate_vals, T, E, K, C)

    # gather -> (E, C, D); padded row reads zeros
    xpad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
    xe = xpad[buf]                                            # (E, C, D)
    xe = pshard(xe, "moe_ecd")

    # --- batched per-expert GEMMs ------------------------------------------------
    wg = wcast(params["w_gate"], xe.dtype)
    wu = wcast(params["w_up"], xe.dtype)
    wd = wcast(params["w_down"], xe.dtype)
    g = jnp.einsum("ecd,edf->ecf", xe, wg)
    u = jnp.einsum("ecd,edf->ecf", xe, wu)
    act = jax.nn.gelu(g) if cfg.activation == "geglu" else jax.nn.silu(g)
    h = pshard(act * u, "moe_ecf")
    ye = jnp.einsum("ecf,efd->ecd", h, wd)  # (E, C, D)
    ye = ye * gbuf[..., None].astype(ye.dtype)

    # --- combine: scatter-add back to tokens ---------------------------------------
    yf = jnp.zeros((T + 1, D), ye.dtype).at[buf.reshape(-1)].add(
        ye.reshape(E * C, D))[:T]
    y = yf.reshape(B, S, D)

    if "shared" in params:
        y = y + mlp(params["shared"], x, cfg.activation)
    return y, aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism: explicit all-to-all dispatch
# ---------------------------------------------------------------------------
#
# The GSPMD path above routes with a token gather, which the partitioner
# lowers to an all-gather of ALL tokens onto every expert shard (the
# "Involuntary full rematerialization" warnings in the dry-run logs).
# Here we write the EP collectives by hand: each data shard routes its
# local tokens, all-to-all exchanges capacity-bounded expert blocks, local
# experts compute, a second all-to-all returns outputs, and the source
# shard combines.  Per-chip link bytes drop from O(T·D) all-gather to
# O(T_local·K·cf·D) all-to-all.


def _moe_shard_map(params, x: jax.Array, cfg: ModelConfig, ctx):
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    pol = ctx.pol
    ep = pol.ep_axes
    tp = pol.tp_axis
    dp = pol.dp_axes
    E, K, D = cfg.num_experts, cfg.experts_per_token, cfg.d_model
    n_ep = 1
    for a in ep:
        n_ep *= mesh.shape[a]
    if E % n_ep or (mesh.shape[tp] > 1 and cfg.moe_d_ff % mesh.shape[tp]) \
            or is_quantized(params["w_gate"]):
        return _moe_gspmd(params, x, cfg)   # shapes don't tile; fall back

    B, S, _ = x.shape
    ep_name = ep if len(ep) > 1 else ep[0]

    def body(router, wg, wu, wd, xl):
        # xl: (B_local, S, D); experts local: (E_local, D, F_local)
        Bl = xl.shape[0]
        Tl = Bl * S
        xf = xl.reshape(Tl, D)
        gate_vals, expert_idx, aux = _route({"router": router}, xf, cfg)
        C = max(1, int(cfg.capacity_factor * Tl * K / E))
        buf, gbuf = _dispatch_tables(expert_idx, gate_vals, Tl, E, K, C)
        xpad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
        xe = xpad[buf]                                    # (E, C, D)
        # exchange: every shard sends each expert-block home
        xe = jax.lax.all_to_all(xe, ep_name, split_axis=0, concat_axis=1,
                                tiled=True)               # (E_l, C·n_ep, D)
        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
        act = jax.nn.gelu(g) if cfg.activation == "geglu" else jax.nn.silu(g)
        ye = jnp.einsum("ecf,efd->ecd", act * u, wd.astype(xe.dtype))
        # return trip; outputs are partial over the tp axis (F was sharded)
        ye = jax.lax.all_to_all(ye, ep_name, split_axis=1, concat_axis=0,
                                tiled=True)               # (E, C, D) partial
        ye = ye * gbuf[..., None].astype(ye.dtype)
        yf = jnp.zeros((Tl + 1, D), ye.dtype).at[buf.reshape(-1)].add(
            ye.reshape(-1, D))[:Tl]
        if mesh.shape[tp] > 1:
            yf = jax.lax.psum(yf, tp)
        aux = jax.lax.pmean(aux, ep_name)
        return yf.reshape(Bl, S, D), aux

    # batch axes not in ep stay as extra DP; specs mention them so the body
    # sees per-shard blocks
    extra_dp = tuple(a for a in dp if a not in ep)
    xspec = P(tuple(extra_dp) + tuple(ep) if extra_dp else ep, None, None)
    yspec = xspec
    specs = dict(
        in_specs=(P(), P(ep, None, tp), P(ep, None, tp), P(ep, tp, None),
                  xspec),
        out_specs=(yspec, P()))
    mapped = jax.shard_map(body, mesh=mesh, check_vma=False, **specs)
    out = mapped(params["router"], params["w_gate"], params["w_up"],
                 params["w_down"], x)
    y, aux = out
    if "shared" in params:
        y = y + mlp(params["shared"], x, cfg.activation)
    return y, aux
