"""Share of device busy time inside the paged kernel's walks of the window
pool (``paged_window_walk``: a "gqa" model's "swa" layers, decode)."""
from benchmark.layer_metrics import _hybrid


def read(obs):
    return _hybrid.time_share(obs, "swa")
