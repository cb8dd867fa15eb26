"""Host microseconds per completed op spent in the node (core/node.py,
core/coordination.py): the layer's share
of the stack samples, times the traced window's wall, over the ops
completed in it."""

from bench.stacks import us_per_op


def read(obs):
    return us_per_op(obs, "node")
