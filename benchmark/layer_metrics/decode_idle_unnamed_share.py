"""Device idle under no host event at all (the guard that the spans still
cover the engine loop), in the cell judged on tokens per second."""
from benchmark.layer_metrics import _gaps


def read(obs):
    return _gaps.share(obs, "unnamed")
