"""Share of the traced window in which no operation ran on the device."""


def read(obs):
    t = obs.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100.0
