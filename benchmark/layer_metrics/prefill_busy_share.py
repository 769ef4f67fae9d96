"""Share of device busy time inside the prefill programs
(``jit__prefill/...``: every op of them, the flash forward and the experts'
grouped matmuls included), for a cell whose prompts are long enough that
prefill and not decode may be the larger part of a reply's cost."""
from benchmark.layer_metrics._kernels import PROGRAM

PREFILL = PROGRAM["flash"] + "/"                 # jit__prefill/<op>


def seconds(obs):
    """Seconds of the traced stretch inside the prefill programs; None
    without a trace or without a prefill in it."""
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(PREFILL)]
    return sum(hit) if hit else None


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]
