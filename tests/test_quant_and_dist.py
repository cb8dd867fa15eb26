"""Weight-only quantization + distribution-layer unit tests."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import decode_step, forward, init_cache, init_params
from repro.models.quant import (dequantize_tree, is_quantized,
                                quantize_tree, quantize_weight, wcast)
from repro.launch.shapes import make_batch, make_decode_tokens

# children stay on the CPU: the parent process may hold the chip
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((256, 128)) * 0.05, jnp.float32)
    q = quantize_weight(w)
    assert q["q"].dtype == jnp.int8
    assert q["s"].shape == (128,)
    back = wcast(q, jnp.float32)
    err = jnp.max(jnp.abs(back - w))
    assert float(err) <= float(jnp.max(jnp.abs(w))) / 127.0 + 1e-7


def test_quantized_forward_close_to_dense():
    cfg = smoke_config("smollm-360m").scaled(remat=False, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_tree(params)
    # embeddings stay dense; attention/mlp weights quantized
    assert is_quantized(qparams["layers"]["attn"]["wq"])
    assert not is_quantized(qparams["embed"])
    rng = np.random.default_rng(1)
    batch = make_batch(cfg, rng, batch=2, seq=16)
    ref, _, _ = forward(params, batch, cfg)
    out, _, _ = forward(qparams, batch, cfg)
    # W8A16-style error: small relative to logit scale
    denom = float(jnp.std(ref)) + 1e-9
    rel = float(jnp.max(jnp.abs(out - ref))) / denom
    assert rel < 0.25, f"quantized logits too far off ({rel})"


@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-2.7b"])
def test_quantized_decode_runs(arch):
    cfg = smoke_config(arch).scaled(remat=False, dtype="float32")
    params = quantize_tree(init_params(jax.random.PRNGKey(0), cfg))
    cache = init_cache(cfg, 2, 32)
    rng = np.random.default_rng(2)
    tok = make_decode_tokens(cfg, rng, 2)
    logits, cache = decode_step(params, cache, tok, cfg)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_remat_policy_dots_matches_full():
    cfg = smoke_config("gemma-7b").scaled(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    batch = make_batch(cfg, rng, batch=2, seq=16)
    from repro.models import loss_fn

    g_full = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    cfg2 = cfg.scaled(remat_policy="dots")
    g_dots = jax.grad(lambda p: loss_fn(p, batch, cfg2)[0])(params)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_dots)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.dist.sharding import MeshContext, ShardingPolicy
    from repro.launch.mesh import make_mesh
    from repro.models.moe import init_moe, moe_ffn

    cfg = smoke_config("kimi-k2-1t-a32b").scaled(
        dtype="float32", num_experts=8, moe_d_ff=64, capacity_factor=8.0,
        shared_expert_d_ff=0)
    mesh = make_mesh((4, 2), ("data", "model"))
    pol = ShardingPolicy.for_mesh(mesh)
    params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 16, cfg.d_model)), jnp.float32)
    with MeshContext(mesh, cfg, pol):
        y1, _ = jax.jit(lambda p, x: moe_ffn(
            p, x, cfg.scaled(moe_impl="gspmd")))(params, x)
        y2, _ = jax.jit(lambda p, x: moe_ffn(
            p, x, cfg.scaled(moe_impl="shard_map")))(params, x)
        # gradients flow through the explicit all-to-alls
        g = jax.jit(jax.grad(lambda p: moe_ffn(
            p, x, cfg.scaled(moe_impl="shard_map"))[0].sum()))(params)
    err = float(jnp.max(jnp.abs(y1 - y2)))
    assert err < 1e-5, err
    for leaf in jax.tree.leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    print("SHARD_MAP_OK", err)
""")


def test_shard_map_moe_matches_gspmd_on_8_devices():
    """Runs in a subprocess: needs 8 host devices while the main test
    process is locked to 1."""
    r = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       cwd=".", env=CPU_ENV)
    assert "SHARD_MAP_OK" in r.stdout, r.stdout + r.stderr
