"""Modeled milliseconds from the takeover of a range to its leader opening
for writes (`leader_takeover` to `leader_open`), mean over the ranges the
crashes of the counted fault periods took the leader of."""

from bench.recovery import phase_ms


def read(obs):
    return phase_ms(obs, "leader_takeover", "leader_open")
