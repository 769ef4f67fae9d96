"""Device idle because the queue was empty (the open loop runs below its
knee): idle that no change to the engine loop can take away."""
from benchmark.layer_metrics import _gaps


def read(obs):
    return _gaps.share(obs, "no_work")
