"""Device idle while the driver thread was inside the engine's own code, in
the cell judged on latency."""
from benchmark.layer_metrics import _gaps


def read(obs):
    return _gaps.share(obs, "host")
