"""Pipeline parallelism: GPipe schedule must be exact vs the sequential
stack (runs on 8 host devices in a subprocess)."""

import os
import subprocess
import sys
import textwrap

from repro.dist.pipeline import bubble_fraction, pp_vs_dp_napkin

# children stay on the CPU: the parent process may hold the chip
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == 0.75
    assert abs(bubble_fraction(15, 2) - 1 / 16) < 1e-9
    assert bubble_fraction(100, 2) < 0.01


def test_pp_vs_dp_napkin_two_pods():
    # mistral-large grads bf16 = 246 GB over 25 GB/s DCN vs a 2-stage
    # pipeline bubble on a ~1 s step: PP wins only with enough microbatches
    r = pp_vs_dp_napkin(grad_bytes=246e9, dcn_bw=25e9 * 256,
                        step_compute_s=1.0, n_micro=16, n_stages=2)
    assert "pp_wins" in r and r["bubble_s"] > 0


PIPE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.pipeline import gpipe
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("pipe", "model"))
    D = 32
    n_stages, layers_per_stage = 4, 2
    rng = np.random.default_rng(0)
    # stage params: (n_stages, layers_per_stage, D, D)
    Ws = jnp.asarray(rng.standard_normal(
        (n_stages, layers_per_stage, D, D)) * 0.2, jnp.float32)

    def stage_fn(Wstage, x):
        for i in range(layers_per_stage):
            x = jnp.tanh(x @ Wstage[i])
        return x

    n_micro, mb = 6, 3
    x = jnp.asarray(rng.standard_normal((n_micro, mb, D)), jnp.float32)

    run = gpipe(stage_fn, mesh, axis="pipe")
    y_pipe = jax.jit(run)(Ws, x)

    # sequential oracle
    y_ref = x
    for s in range(n_stages):
        y_ref = jax.vmap(lambda xm: stage_fn(Ws[s], xm))(y_ref)

    err = float(jnp.max(jnp.abs(y_pipe - y_ref)))
    assert err < 1e-5, err
    # collective-permute must appear in the lowered module
    txt = jax.jit(run).lower(Ws, x).compile().as_text()
    assert "collective-permute" in txt
    print("PIPE_OK", err)
""")


def test_gpipe_exact_vs_sequential_subprocess():
    r = subprocess.run([sys.executable, "-c", PIPE_SCRIPT],
                       capture_output=True, text=True, timeout=600, cwd=".",
                       env=CPU_ENV)
    assert "PIPE_OK" in r.stdout, r.stdout + r.stderr
