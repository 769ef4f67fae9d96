"""The latent decode kernel's share of its roofline: what the window's
context tokens and lane-steps need through the "mla" layers
(``_latent.cost``, from the program's counters) against the time the
``latent_paged`` kernels took in the trace."""
from benchmark.layer_metrics._latent import roofline as read  # noqa: F401
