"""Share of device busy time inside the expert FFN's grouped-matmul kernels
(``ops/moe.py``; jax's Pallas ``gmm``) of the decode and prefill programs."""
from benchmark.layer_metrics import _moe


def read(obs):
    s = _moe.kernel_seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]
