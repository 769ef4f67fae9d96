"""Shared by the looped-stack readers: what a decode step of a stack that
runs several times must read, from the configuration's shapes and the
loop's records, and which trace ops are the decode program.

A model with ``loop_steps`` = R sends every token through its ``num_layers``
layers R times, each pass with a K/V cache of its own (``R x num_layers``
cache layers). One inner decode step, whatever the number of live lanes,
streams the stack's weights R times (q, k, v, o, the gated FFN's three
matrices and the layer's norms; nothing of a pass's weights is left on the
chip when the next begins: one pass is 4.9 GB at the published widths), the
head once, and every live lane's cached K and V in all R x num_layers cache
layers. The embedding is a gather of a row a lane and is left out.

The live K/V comes from the loop's records: such a model books in
``live_blocks`` a walk a live lane, step and PASS (``ceil(context /
block_size)`` each), and ``lane_steps`` a live lane and step. A lane's last
block holds at least one token and the others are full, so the tokens a
pass reads are at least ``(walks - lanes) x block_size + lanes``: the bytes
are counted from that LOWER bound, so the share reads low, never high. A
token is ``2 x num_heads x head_dim`` values (K and V) a cache layer.

FLOPs: two a weight of the stack a lane, step and pass, and the head's once
(attention's own are a thousandth of that at these contexts and are left
out): at 16 lanes a twentieth of the time the bytes take, so the bound is
the memory's in every cell there is.

The decode program's seconds are the device time of every op under
``jit__decode`` in the traced stretch (the engine names the function
``_decode`` whatever runs inside), crossed with the window's records the
way ``paged_attn_us_per_live_block`` crosses the two clocks: bytes per
second of window over seconds per second of traced stretch.
"""
from benchmark import flops
from benchmark.layer_metrics import _loop
from benchmark.layer_metrics._kernels import PROGRAM

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def passes(model):
    return int(model.get("loop_steps", 1))


def stack_params(model):
    """Weights of ONE pass through the stack: per layer q, k, v and o,
    the FFN's matrices (three if gated) and the norms' gammas."""
    m, f = model["model_dim"], model["ffn_dim"]
    hm = model["num_heads"] * model.get("head_dim", m // model["num_heads"])
    norms = (4 if model.get("post_norm") else 2) * m
    ffn = (3 if model.get("ffn_gated") else 2) * m * f
    return model["num_layers"] * (4 * hm * m + ffn + norms)


def step_cost(model, steps, lane_steps, live_blocks, block_size,
              w_itemsize=2, kv_itemsize=2):
    """(flops, bytes) of ``steps`` inner decode steps that advanced
    ``lane_steps`` live lanes and booked ``live_blocks`` walks (a lane, step
    and pass)."""
    r = passes(model)
    head = model["vocab"] * model["model_dim"]
    hm = model["num_heads"] * model.get(
        "head_dim", model["model_dim"] // model["num_heads"])
    tokens = max(live_blocks - r * lane_steps, 0) * block_size \
        + r * lane_steps
    nbytes = (steps * (r * stack_params(model) + head) * w_itemsize
              + model["num_layers"] * tokens * 2 * hm * kv_itemsize)
    return 2.0 * lane_steps * (r * stack_params(model) + head), float(nbytes)


def decode_seconds(obs):
    """Device seconds of the traced stretch in the decode program's ops;
    None without a trace or without the program in it."""
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(PROGRAM["paged"])]
    return sum(hit) if hit else None


def passes_per_step(obs):
    """Stack passes the window's programs ran over the program steps that
    ran them (a prefill, an inner decode step); None for a model whose stack
    runs once, a program whose records carry no ``passes`` and a window
    without a step."""
    recs = _loop.records(obs)
    model = obs.get("config", {}).get("model", {})
    if recs is None or passes(model) < 2 or not hasattr(recs[0], "passes"):
        return None
    steps = _loop.total(recs, "chunk_steps") + _loop.total(recs, "prefills")
    return _loop.total(recs, "passes") / float(steps) if steps else None


def decode_roofline(obs):
    """Percent: the least time the chip could take for the window's inner
    decode steps, per second of window, over the decode program's device
    seconds per second of traced stretch."""
    recs, seconds = _loop.records(obs), decode_seconds(obs)
    cfg = obs.get("config", {})
    model = cfg.get("model", {})
    if recs is None or seconds is None or not obs.get("peak") \
            or passes(model) < 2:
        return None
    steps = _loop.total(recs, "chunk_steps")
    if not steps:
        return None
    engine = cfg["engine"]
    fl, nbytes = step_cost(
        model, steps, _loop.total(recs, "lane_steps"),
        _loop.total(recs, "live_blocks"), engine["block_size"],
        ITEMSIZE[cfg["weights_dtype"]], ITEMSIZE[engine["kv_dtype"]])
    w = _loop.seconds(obs)
    share, _bound = flops.roofline_share(
        fl / w, nbytes / w, seconds / obs["trace"]["window_s"], obs["peak"])
    return share
