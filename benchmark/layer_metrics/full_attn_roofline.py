"""The full walks' share of their roofline: what the window's keys and
lane-steps need through the "full" layers (``_hybrid.cost``, from the loop
records' counters) against the time the ``paged_full_walk`` kernels took."""
from benchmark.layer_metrics import _hybrid


def read(obs):
    return _hybrid.roofline(obs, "full")
