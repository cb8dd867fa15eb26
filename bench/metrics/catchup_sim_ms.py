"""Modeled milliseconds from a crashed node's restart to each of its
replicas being back in its leader's in-sync set (`node_restart` to
`follower_caught_up`), mean over the replicas of the restarts in the
counted fault periods."""

from bench.failover import catchup_ms


def read(obs):
    return catchup_ms(obs)
