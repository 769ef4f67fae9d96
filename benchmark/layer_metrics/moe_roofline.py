"""The expert FFN's share of its roofline: what the window's pairs and
touched experts need (``_moe.cost``, from the program's counters) against
the time its kernels took in the trace."""
from benchmark.layer_metrics._moe import roofline as read  # noqa: F401
