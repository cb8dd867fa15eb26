"""The numpy reference of the sampler's draw against the program's
`OpStream`, on the CPU."""

import numpy as np
import pytest

from bench import cells, harness
from bench.sampler_ref import SamplerReference, threefry2x32

BATCHES = 3


@pytest.mark.parametrize("name,seed", [
    ("s9-strong-closed32", 0), ("ycsb-a-closed32", 2**31 - 5),
    ("ycsb-b-closed32", 123456789)])
def test_reference_matches_the_program(name, seed):
    spec = harness.workload_spec(cells.load_cell(name))
    stream = harness.RecordingStream(spec, seed=seed)
    ref = SamplerReference(seed, harness.PLACEMENT_SEED, spec.num_keys,
                           spec.zipf_theta, list(spec.mix()),
                           spec.value_size, stream.batch)
    for _ in range(BATCHES):
        stream._refill()
    for i, (keys, ops, vsz, gaps) in enumerate(stream.batches):
        rk, ro, rv, rg = ref.batch(i)
        np.testing.assert_array_equal(keys, rk)
        np.testing.assert_array_equal(ops, ro)
        np.testing.assert_array_equal(vsz, rv)
        np.testing.assert_allclose(gaps, rg, rtol=1e-6, atol=2e-7)


def test_threefry_known_answer():
    # Salmon et al.'s known-answer vector for Threefry-2x32, 20 rounds
    x = threefry2x32(0x13198A2E, 0x03707344,
                     np.array([0x243F6A88], np.uint32),
                     np.array([0x85A308D3], np.uint32))
    assert (int(x[0][0]), int(x[1][0])) == (0xC4923A9C, 0x483DF7A0)


def test_lower_precision_draw_differs():
    import ml_dtypes
    spec = harness.workload_spec(cells.load_cell("s9-strong-closed32"))
    args = (7, harness.PLACEMENT_SEED, spec.num_keys, spec.zipf_theta,
            list(spec.mix()), spec.value_size, 8192)
    f32, bf16 = SamplerReference(*args).batch(0), SamplerReference(
        *args, dtype=ml_dtypes.bfloat16).batch(0)
    assert np.count_nonzero(f32[0] != bf16[0]) > 1000
    assert np.isfinite(bf16[3].astype(np.float32)).all()
