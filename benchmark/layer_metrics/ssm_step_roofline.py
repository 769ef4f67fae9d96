"""The decode state update's share of its roofline: what the window's live
stream-steps need through the nine state-space layers (``_ssm.step_cost``,
from the decode-batch histogram) against the time the ``ssm_step`` kernels
took in the trace."""
from benchmark.layer_metrics._ssm import step_roofline as read  # noqa: F401
