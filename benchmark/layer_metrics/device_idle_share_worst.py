"""Idle share of the idlest chip of a training cell on several chips."""
from benchmark.layer_metrics import _idle


def read(obs):
    return _idle.worst_idle(obs) if obs["chips"] > 1 else None
