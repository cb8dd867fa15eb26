"""Host microseconds per completed op spent in the replica protocol
(core/replica.py, wal.py, storage.py, txn.py, types.py): the layer's share
of the stack samples, times the traced window's wall, over the ops
completed in it."""

from bench.stacks import us_per_op


def read(obs):
    return us_per_op(obs, "protocol")
