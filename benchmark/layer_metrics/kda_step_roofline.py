"""The decode state update's share of its roofline: what the window's
lane-steps need through the linear layers (``_linear.step_cost``: the float32
state read and written, the token's rows; from the loop records'
``lane_steps``) against the time the ``kda_step`` kernels took."""
from benchmark.layer_metrics import _linear


def read(obs):
    return _linear.roofline(obs, "step")
