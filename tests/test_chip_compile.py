"""The workload sampler compiles for a TPU v5e chip that is described, not
attached: the TPU compiler refuses here what it would refuse on the chip.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.workload import OpStream, WorkloadSpec, generators

BATCH = 8192

CASES = {
    # the bench's deployment keyspace (spinnaker_bench.base_spec)
    "zipf_5000": WorkloadSpec(num_keys=5000),
    # the chip smoke's keyspace: largest with an exact float32 CDF
    "zipf_1m": WorkloadSpec(num_keys=1_000_000),
    "uniform_value_range": WorkloadSpec(num_keys=1_000_000,
                                        key_dist="uniform",
                                        value_size_dist="uniform"),
}


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip, so keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sampler_args(spec: WorkloadSpec, sharding):
    """Shapes of the arguments `OpStream._refill` passes, on `sharding`."""
    s = OpStream(spec, batch=BATCH)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    args = (sds(jax.random.PRNGKey(0)), None if s._cdf is None else
            sds(s._cdf), sds(s._mix_cdf))
    static = dict(num_keys=spec.num_keys, vfix=spec.value_size,
                  vmin=s._vmin, vmax=s._vmax, batch=BATCH)
    return args, static


@pytest.mark.parametrize("name", list(CASES))
def test_sample_batch_compiles_for_v5e(name, one_chip):
    spec = CASES[name]
    args, static = _sampler_args(spec, one_chip)
    compiled = generators._sample_batch.lower(*args, **static).compile()
    outs = compiled.out_info
    assert [o.shape for o in outs] == [(BATCH,)] * 4
    assert [o.dtype for o in outs] == [jnp.int32] * 3 + [jnp.float32]
    mem = compiled.memory_analysis()
    cdf_bytes = 0 if args[1] is None else 4 * spec.num_keys
    assert mem.argument_size_in_bytes >= cdf_bytes
    # the sampler's whole working set is far below one chip's 16 GB
    assert mem.temp_size_in_bytes < 64 << 20
