"""Idle share of the device in a training cell (mean over the chips)."""
from benchmark.layer_metrics._idle import mean_idle as read  # noqa: F401
