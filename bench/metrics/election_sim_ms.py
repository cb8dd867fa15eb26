"""Modeled milliseconds from a leader's crash to the takeover on each
range it led (`leader_takeover` in the cluster's event log), mean over
those ranges in the counted fault periods."""

from bench.recovery import phase_ms


def read(obs):
    return phase_ms(obs, "node_crash", "leader_takeover")
