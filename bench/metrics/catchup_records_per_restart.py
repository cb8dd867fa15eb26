"""Log records the leaders sent a restarted node's replicas to catch
them up (`follower_caught_up` in the cluster's event log), per restart
in the counted fault periods."""

from bench.failover import catchup_records_per_restart


def read(obs):
    return catchup_records_per_restart(obs)
