"""The sampler's share of its roofline: the least time the chip needs to
write one batch's outputs (bytes over peak HBM bandwidth), over the
measured device time of one run.  Only the outputs are counted, which any
implementation must write, so the share stays a lower bound."""

from bench.peaks import peak, sampler_output_bytes


def read(obs):
    t = obs.trace
    if t is None or not t.sampler_runs or not t.sampler_s:
        return None
    least = sampler_output_bytes(obs.sampler_batch) / peak(
        obs.device_kind, "hbm_bytes_per_s")
    return least / (t.sampler_s / t.sampler_runs) * 100.0
