"""Shared by the readers of a model with gated delta-rule linear-attention
layers (``model.layer_kinds`` "kda"): what the two kernels of ``ops/kda.py``
need, from the program's counters, and which trace ops they are.

The ops: the Pallas kernels carry their own names, ``jit__decode/kda_step.<n>``
(one rank-one update of a stream's matrix state, a call a "kda" layer and
decode step) and ``jit__prefill/kda_chunk.<n>`` (a prompt's chunkwise pass, a
call a "kda" layer). The projections, the conv over the stream's tail, the
heads' norms, the decay's softplus and the output gate are XLA ops without a
name of their own and are NOT in these seconds.

The counters ride on the engine loop's records (``serving/obs.py``
``LoopRecord``, windowed as ``_loop.py`` windows them): ``lane_steps`` — live
lanes summed over decode steps, each of which rewrites one state a "kda"
layer; ``prefill_tokens`` — the prompt tokens the window prefilled (the
kernel skips the chunks of a rung's padding, so a prompt's own rows are its
work: pricing the rung's rows would count padding as useful). A program
without a "kda" layer has no such op on its trace, and every reader here
then returns None.

Cost (the algorithm's, for H heads of dk keys and dv values, float32 state,
two-byte activations). A decode update of one stream in one layer reads and
writes the state, ``2 x 4 H dk dv`` bytes (8,388,608 at 64 x 128 x 128), and
moves the token's rows: q, k and v in two bytes, the decay in four, ``beta``
in four, ``o`` in two; ``7 H dk dv`` FLOPs (the decay, the row ``k^T S`` and
``S^T q`` at two each, the rank-one update's multiply-add one: 7,340,032). A
prefilled ROW is priced as the recurrence's: the same FLOPs and the same
rows' bytes, and the state written once a prompt. The chunkwise form does
MORE arithmetic than the recurrence (the pairs' scores, the triangular
system) and the kernels take their rows widened or folded (``beta k``,
``beta v``, columns a head block), so both shares read low, never high.
"""
from benchmark import flops
from benchmark.layer_metrics import _loop
from benchmark.layer_metrics._kernels import PROGRAM

KERNEL = {"step": PROGRAM["paged"] + "/kda_step",
          "chunk": PROGRAM["flash"] + "/kda_chunk"}


def layers(model):
    return sum(k == "kda" for k in model.get("layer_kinds") or ())


def _sizes(model):
    h = model.get("kda_heads", model["num_heads"])
    dk = model.get("kda_head_dim", model["head_dim"])
    return h, dk, dk


def row_bytes(model, itemsize=2):
    """One token's rows through one layer's kernel: q, k, v and o in the
    activations' bytes, the decay a key channel and ``beta`` in four."""
    h, dk, dv = _sizes(model)
    return h * ((2 * dk + 2 * dv) * itemsize + 4 * dk + 4)


def step_cost(lane_steps, model):
    """(flops, bytes) of ``lane_steps`` decode updates through every "kda"
    layer of ``model`` (a configuration file's ``model`` object)."""
    h, dk, dv = _sizes(model)
    updates = lane_steps * layers(model)
    return (7.0 * h * dk * dv * updates,
            float(2 * 4 * h * dk * dv + row_bytes(model)) * updates)


def chunk_cost(rows, prompts, model):
    """(flops, bytes) of ``rows`` prefilled rows of ``prompts`` prompts
    through every "kda" layer of ``model``."""
    h, dk, dv = _sizes(model)
    n = layers(model)
    return (7.0 * h * dk * dv * rows * n,
            float(row_bytes(model) * rows + 4 * h * dk * dv * prompts) * n)


def kernel_seconds(obs, which):
    """Seconds of the traced stretch inside the kernel; None without a
    trace or without such an op (a program from before it, another
    model's)."""
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(KERNEL[which])]
    return sum(hit) if hit else None


def time_share(obs, which):
    s = kernel_seconds(obs, which)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]


def roofline(obs, which):
    """Percent: the least time the chip could take for the window's decode
    updates (``which`` "step") or prefilled rows ("chunk"), per second of
    window, over the kernel's seconds per second of traced stretch. None
    without the kernel on the trace, without the loop's records or their
    counter, and where the window ran no such work."""
    seconds, recs = kernel_seconds(obs, which), _loop.records(obs)
    model = obs.get("config", {}).get("model", {})
    if seconds is None or not recs or not obs.get("peak") \
            or not layers(model):
        return None
    if which == "step":
        n = _loop.total(recs, "lane_steps")
        cost = step_cost(n, model)
    else:
        if not hasattr(recs[0], "prefill_tokens"):
            return None
        n = _loop.total(recs, "prefill_tokens")
        cost = chunk_cost(n, _loop.total(recs, "prefills"), model)
    if n <= 0:
        return None
    w = _loop.seconds(obs)
    share, _bound = flops.roofline_share(
        cost[0] / w, cost[1] / w, seconds / obs["trace"]["window_s"],
        obs["peak"])
    return share
