"""A looped stack's decode step against its roofline: what the window's
inner decode steps must read — the stack's weights once a pass, the head,
the live K/V of every cache layer (``_looped.step_cost``, from the loop's
records) — against the device time of the decode program in the trace."""
from benchmark.layer_metrics._looped import decode_roofline as read  # noqa: F401
