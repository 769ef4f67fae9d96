"""Experts that received at least one live token, per layer-step (of
``num_experts``): how many experts' weights a layer had to read."""
from benchmark.layer_metrics import _moe


def read(obs):
    d = _moe.delta(obs)
    return None if d is None else d["experts_touched"] / d["layer_steps"]
