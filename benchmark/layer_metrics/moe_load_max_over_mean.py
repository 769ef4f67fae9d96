"""Busiest expert's tokens over the mean expert's across the window, per
layer, averaged over the layers: 1 is a perfectly even router."""
from benchmark.layer_metrics import _moe


def read(obs):
    d = _moe.delta(obs)
    if d is None:
        return None
    ratios = [max(row) * len(row) / sum(row)
              for row in d["tokens_per_expert"] if sum(row) > 0]
    return sum(ratios) / len(ratios) if ratios else None
