"""Device microseconds per run of the workload sampler's program, from the
profiler trace of the window."""


def read(obs):
    t = obs.trace
    if t is None or not t.sampler_runs:
        return None
    return t.sampler_s / t.sampler_runs * 1e6
