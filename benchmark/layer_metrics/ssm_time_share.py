"""Share of device busy time inside the state-space kernels (``ops/ssm.py``):
``ssm_step`` of the decode programs and ``ssm_scan`` of the prefill
programs, by the names their custom calls carry."""
from benchmark.layer_metrics import _ssm


def read(obs):
    s = _ssm.kernel_seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]
