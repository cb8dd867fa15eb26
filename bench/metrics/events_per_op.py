"""Simulator events processed per completed op in the traced window."""


def read(obs):
    return obs.events / obs.ops_ok if obs.ops_ok else None
