"""Shared by the state-space readers: what the selective-scan kernels need,
from the program's counters, and which trace ops they are.

The ops: ``ops/ssm.py``'s Pallas kernels carry their own names —
``jit__decode/ssm_step.<n>`` (one state update for each live stream and
"mamba" layer) and ``jit__prefill/ssm_scan.<n>`` (a prompt's scan) — as jax's
``gmm`` does. The conv, the two small projections between it and the update
(``W_x``, ``W_dt``) and the in/out projections are XLA ops without a name of
their own and are NOT in these seconds.

The decode update's cost (the algorithm's), for one stream and one layer of
``Dn = ssm_expand x model_dim`` channels and ``N = ssm_state`` states: the
float32 state read and written, ``2 x 4 N Dn`` bytes; the token's rows in —
``x`` and ``z`` in the activations' two bytes, ``dt`` in four — and ``y`` and
the gated output back, ``(2 + 2 + 4 + 2 + 2) Dn``; ``B`` and ``C``,
``2 x 4 N``. The conv tail (``2 x 3 Dn`` two-byte values read and written a
step) is moved by XLA fusions outside the named kernel, so it is left out of
the bytes as its time is out of the seconds. Seven FLOPs a state element
(exp counted as one). The kernel takes its rows widened to float32 today, so
it moves somewhat MORE than is counted: the share reads low for that, never
high.

Live stream-steps come from the ``serving.decode_batch`` histogram's sum,
which ``snapshot()`` carries at the window's two ends: each decode step
observes the number of live streams it advanced.
"""
from benchmark import flops
from benchmark.layer_metrics._kernels import PROGRAM

STEP = PROGRAM["paged"] + "/ssm_step"       # jit__decode/ssm_step.<n>
SCAN = PROGRAM["flash"] + "/ssm_scan"       # jit__prefill/ssm_scan.<n>


def state_layers(model):
    return sum(k == "mamba" for k in model.get("layer_kinds") or ())


def step_cost(stream_steps, model, act_itemsize=2):
    """(flops, bytes) of ``stream_steps`` decode updates through every
    "mamba" layer of ``model`` (a configuration file's ``model`` object)."""
    dn = model["ssm_expand"] * model["model_dim"]
    n = model["ssm_state"]
    per = (2 * 4 * n * dn + (4 * act_itemsize + 4) * dn + 2 * 4 * n)
    updates = stream_steps * state_layers(model)
    return 7.0 * n * dn * updates, float(per) * updates


def stream_steps(obs):
    """Live stream-steps of the window, or None where the snapshots do not
    carry the decode-batch histogram."""
    try:
        return (obs["after"]["decode_batch"][1]
                - obs["before"]["decode_batch"][1])
    except (KeyError, TypeError, IndexError):
        return None


def kernel_seconds(obs, prefixes=(STEP, SCAN)):
    """Seconds of the traced stretch inside the named state-space kernels;
    None without a trace or without such an op (a program from before
    them)."""
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(tuple(prefixes))]
    return sum(hit) if hit else None


def step_roofline(obs):
    """Percent: the least time the chip could take for the window's decode
    state updates, per second of window, over the ``ssm_step`` kernels'
    seconds per second of traced stretch."""
    steps, seconds = stream_steps(obs), kernel_seconds(obs, (STEP,))
    model = obs.get("config", {}).get("model", {})
    if not steps or seconds is None or not obs.get("peak") \
            or not state_layers(model):
        return None
    fl, nbytes = step_cost(steps, model)
    w = obs["window_s"]
    share, _bound = flops.roofline_share(
        fl / w, nbytes / w, seconds / obs["trace"]["window_s"], obs["peak"])
    return share
