"""Elastic re-meshing (restore onto a different mesh) and gradient
accumulation equivalence."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.data.pipeline import DataConfig, TokenStream
from repro.train.optim import OptimizerConfig
from repro.train.step import TrainConfig, init_train_state, make_train_step

# children stay on the CPU: the parent process may hold the chip
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_microbatch_accumulation_matches_full_batch():
    cfg = smoke_config("smollm-360m").scaled(remat=False, dtype="float32")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                      seed=5, mixture_docs=False)
    batch = {k: jnp.asarray(v)
             for k, v in TokenStream(dcfg, 0).batch_at(0).items()}

    t1 = TrainConfig(optimizer=OptimizerConfig(lr=1e-3), microbatches=1)
    t4 = TrainConfig(optimizer=OptimizerConfig(lr=1e-3), microbatches=4)
    s1 = init_train_state(jax.random.PRNGKey(0), cfg, t1)
    s4 = init_train_state(jax.random.PRNGKey(0), cfg, t4)
    s1b, m1 = jax.jit(make_train_step(cfg, t1))(s1, batch)
    s4b, m4 = jax.jit(make_train_step(cfg, t4))(s4, batch)
    assert m4["loss"] == pytest.approx(float(m1["loss"]), rel=1e-5)
    # atol covers f32 reduction-order noise in the per-microbatch grads,
    # amplified by Adam's rsqrt on near-zero second moments at step 1
    for a, b in zip(jax.tree.leaves(s1b["params"]),
                    jax.tree.leaves(s4b["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_grad_compression_step_trains():
    cfg = smoke_config("smollm-360m").scaled(remat=False, dtype="float32")
    tcfg = TrainConfig(optimizer=OptimizerConfig(lr=1e-3),
                       grad_compression=True)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4,
                      seed=6)
    stream = TokenStream(dcfg, 0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    losses = []
    for s in range(8):
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(s).items()}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import smoke_config
    from repro.dist.sharding import MeshContext, ShardingPolicy
    from repro.launch.mesh import make_mesh
    from repro.checkpoint.store import SpinnakerCheckpointStore, StoreConfig
    from repro.data.pipeline import DataConfig, TokenStream
    from repro.train.optim import OptimizerConfig
    from repro.train.step import TrainConfig, init_train_state, make_train_step

    cfg = smoke_config("smollm-360m").scaled(remat=False, dtype="float32")
    tcfg = TrainConfig(optimizer=OptimizerConfig(lr=1e-3))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                      seed=9, mixture_docs=False)
    stream = TokenStream(dcfg, 0)

    def run_on_mesh(mesh, state, start, n):
        pol = ShardingPolicy.for_mesh(mesh)
        with MeshContext(mesh, cfg, pol) as ctx:
            shard = ctx.param_shardings(
                jax.eval_shape(lambda: state)["params"]) \
                if False else None
            step = jax.jit(make_train_step(cfg, tcfg))
            losses = []
            for s in range(start, start + n):
                batch = {k: jnp.asarray(v)
                         for k, v in stream.batch_at(s).items()}
                batch = jax.device_put(batch, NamedSharding(
                    mesh, P(("data",), None)))
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        return state, losses

    # phase 1: 8 devices as (4, 2)
    mesh_a = make_mesh((4, 2), ("data", "model"))
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    state, l1 = run_on_mesh(mesh_a, state, 0, 3)

    store = SpinnakerCheckpointStore(StoreConfig(chunk_bytes=1 << 16))
    store.save(3, jax.tree.map(np.asarray, state))

    # "node loss": elastic restart on a (2, 2) mesh of 4 surviving devices
    mesh_b = jax.sharding.Mesh(
        np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    fresh = init_train_state(jax.random.PRNGKey(1), cfg, tcfg)
    step0, restored = store.restore_tree(fresh)
    restored = jax.tree.map(jnp.asarray, restored)
    state_b, l2 = run_on_mesh(mesh_b, restored, step0, 3)

    # reference: uninterrupted single-mesh run
    ref = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    ref, lr1 = run_on_mesh(mesh_a, ref, 0, 3)
    ref, lr2 = run_on_mesh(mesh_a, ref, 3, 3)

    assert np.allclose(l1, lr1, rtol=1e-5), (l1, lr1)
    assert np.allclose(l2, lr2, rtol=1e-4, atol=1e-5), (l2, lr2)
    print("ELASTIC_OK", l2)
""")


def test_elastic_restart_on_smaller_mesh_subprocess():
    """Checkpoint on a (4,2) mesh, restore + resume on (2,2) of the
    survivors: losses must match the uninterrupted run (restore is by
    logical key, resharding-safe)."""
    r = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT],
                       capture_output=True, text=True, timeout=900, cwd=".",
                       env=CPU_ENV)
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr
