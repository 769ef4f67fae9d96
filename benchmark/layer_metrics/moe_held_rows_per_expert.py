"""Rows a held expert computes per layer-step, on average: the window's
pairs over layer-steps and the experts this engine holds
(``model.experts_held``; all of them where it holds every one). A chip of a
deployment whose ranks each decode their own batch gets rows from every
rank; one engine alone sends its own batch's share only."""
from benchmark.layer_metrics import _moe


def read(obs):
    d = _moe.delta(obs)
    if d is None:
        return None
    model = obs["config"]["model"]
    held = (model.get("experts_held") or (0, model["num_experts"]))[1]
    return d["pairs"] / (d["layer_steps"] * held)
