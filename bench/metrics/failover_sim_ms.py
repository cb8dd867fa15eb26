"""Modeled milliseconds from a leader's crash to the first client that
follows the new leader of each range it led (`client_failover` in the
cluster's event log): how long clients kept routing to the dead node.
Mean over those ranges in the counted fault periods."""

from bench.failover import failover_ms


def read(obs):
    return failover_ms(obs)
