"""Idle share of the device in a serving cell judged on latency."""
from benchmark.layer_metrics._idle import mean_idle as read  # noqa: F401
