"""Pipeline parallelism: GPipe schedule over a mesh axis, plus napkin math
for choosing pipeline- vs data-parallelism across a slow interconnect.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """Fraction of device time idle in a GPipe schedule.

    A pipeline of S stages fed M microbatches runs M + S - 1 ticks, of
    which S - 1 per device are fill/drain bubble.
    """
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pp_vs_dp_napkin(grad_bytes: float, dcn_bw: float, step_compute_s: float,
                    n_micro: int, n_stages: int) -> dict:
    """Back-of-envelope: pipeline across a slow link vs data-parallel
    all-reduce over it.

    DP pays a ~2x grad-bytes all-reduce on the link every step; PP pays the
    fill/drain bubble instead (cross-stage activations are ignored — they
    are tiny next to full gradients at napkin precision).
    """
    dp_allreduce_s = 2.0 * grad_bytes / dcn_bw
    bubble_s = step_compute_s * bubble_fraction(n_micro, n_stages)
    return {
        "dp_allreduce_s": dp_allreduce_s,
        "bubble_s": bubble_s,
        "pp_wins": bool(bubble_s < dp_allreduce_s),
        "advantage_s": dp_allreduce_s - bubble_s,
    }


def gpipe(stage_fn: Callable, mesh, axis: str = "pipe") -> Callable:
    """Build a GPipe runner over `axis` of `mesh`.

    `stage_fn(W_stage, x)` applies one pipeline stage.  The returned
    `run(Ws, x)` takes stage-stacked params `Ws: (n_stages, ...)` and
    microbatched inputs `x: (n_micro, mb, ...)`, and equals applying the
    stages sequentially to every microbatch.  Stages are laid out one per
    device along `axis`; activations move between stages with ppermute
    (lowers to collective-permute).
    """
    n_devices = mesh.shape[axis]

    def run(Ws, x):
        n_stages = Ws.shape[0]
        if n_stages != n_devices:
            raise ValueError(
                f"gpipe: {n_stages} stages but mesh axis {axis!r} has "
                f"{n_devices} devices (need exactly one stage per device)")
        n_micro = x.shape[0]
        ticks = n_micro + n_stages - 1
        ring = [(i, (i + 1) % n_devices) for i in range(n_devices)]

        def device_body(W_local, x_all):
            W = W_local[0]                      # this device's stage params
            stage = jax.lax.axis_index(axis)
            state0 = jnp.zeros(x_all.shape[1:], x_all.dtype)
            out0 = jnp.zeros_like(x_all)

            def tick(carry, t):
                state, out = carry
                # stage 0 injects microbatch t; others consume the permuted
                # activation from the previous tick
                x_in = jnp.where(stage == 0,
                                 x_all[jnp.clip(t, 0, n_micro - 1)], state)
                y = stage_fn(W, x_in)
                # the last stage finishes microbatch t - (S - 1) at tick t
                mb_done = t - (n_stages - 1)
                write = (stage == n_stages - 1) & (mb_done >= 0)
                out = jnp.where(
                    write,
                    jax.lax.dynamic_update_index_in_dim(
                        out, y, jnp.clip(mb_done, 0, n_micro - 1), 0),
                    out)
                state = jax.lax.ppermute(y, axis, ring)
                return (state, out), None

            (_, out), _ = jax.lax.scan(tick, (state0, out0),
                                       jnp.arange(ticks))
            return out

        mapped = jax.shard_map(device_body, mesh=mesh,
                               in_specs=(P(axis), P()), out_specs=P(axis),
                               check_vma=False)
        stacked = mapped(Ws, x)       # (n_devices * n_micro, mb, ...)
        return stacked[-n_micro:]     # only the last stage's buffer is real

    return run
