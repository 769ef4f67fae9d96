"""The window walks' share of their roofline: what the window's keys (at
most ``window`` a lane and step) and lane-steps need through the "swa"
layers against the time the ``paged_window_walk`` kernels took."""
from benchmark.layer_metrics import _hybrid


def read(obs):
    return _hybrid.roofline(obs, "swa")
