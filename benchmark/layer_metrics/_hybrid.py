"""Shared by the readers of a model whose "swa" and "full" layers are plain
grouped-query attention (``model.attn_form`` "gqa"): what the paged kernel's
two walks need, from the program's counters, and which trace ops they are.

The ops: the ONE paged kernel of ``ops/attention.py`` under the two names
these layers' calls give it, ``jit__decode/paged_full_walk.<n>`` and
``jit__decode/paged_window_walk.<n>`` (they are not ``branch_0_fun``, so
``paged_attn_time_share`` does not see them), and the flash forward these
layers' prefill calls, ``jit__prefill/flash_gqa_fwd.<n>`` (the K/V head by
index map; the same kernel as ``branch_0_fun`` in the other models' prefill
programs). The projections, the rotation and the cache writes are XLA ops
without a name of their own and are NOT in these seconds.

The counters ride on the engine loop's records (``serving/obs.py``
``LoopRecord``, windowed as ``_loop.py`` windows them): ``full_ctx_tokens``
— the sum, over decode steps and their live lanes, of the keys ONE full
layer's walk read (the lane's context, its own token included);
``window_ctx_tokens`` — the same for ONE window layer, at most ``window`` a
lane; ``lane_steps`` — live lanes summed over decode steps. A step calls the
kernel once a layer. A program without the two counters (the parent of the
PR that added them) has records without those fields: every reader here
then returns None.

Cost (the algorithm's, for one key read by one stream in one layer, H query
heads of hd lanes over Hkv K/V heads, values dv wide, two-byte pages): the
key's K and V rows are read once, ``Hkv x (hd + dv) x 2`` bytes (2,560 in a
full layer, 5,120 in a window layer; the pages keep a K head in a 256-lane
row, 3,072 and 6,144 bytes, and the kernel pays for that, not the roofline);
every query head takes its score against the key and adds the value to its
result, ``2 H (hd + dv)`` FLOPs (40,960). A lane-step and layer also reads
the heads' query rows and writes their results: ``H (hd + dv) x 2`` bytes.
Nothing else is counted, so a share reads low where the kernel does more,
never high.
"""
from benchmark import flops
from benchmark.layer_metrics import _loop
from benchmark.layer_metrics._kernels import PROGRAM

KERNEL = {"full": PROGRAM["paged"] + "/paged_full_walk",
          "swa": PROGRAM["paged"] + "/paged_window_walk",
          "flash": PROGRAM["flash"] + "/flash_gqa_fwd"}
COUNTER = {"full": "full_ctx_tokens", "swa": "window_ctx_tokens"}


def layers(model, kind):
    """Layers of ``kind`` in a "gqa" model; 0 for any other model."""
    if model.get("attn_form") != "gqa":
        return 0
    return sum(k == kind for k in model.get("layer_kinds") or ())


def kv_heads(model, kind):
    return model.get("swa_kv_heads", model["num_kv_heads"]) \
        if kind == "swa" else model["num_kv_heads"]


def cost(ctx_tokens, lane_steps, model, kind, itemsize=2):
    """(flops, bytes) of the window's decode steps through every layer of
    ``kind`` of ``model`` (a configuration file's ``model`` object)."""
    h, hd = model["num_heads"], model["head_dim"]
    dv = model.get("v_dim") or hd
    n = layers(model, kind)
    fl = 2.0 * h * (hd + dv) * ctx_tokens * n
    nbytes = (kv_heads(model, kind) * (hd + dv) * itemsize * ctx_tokens
              + h * (hd + dv) * itemsize * lane_steps) * n
    return fl, float(nbytes)


def delta(obs, kind):
    """``{"ctx_tokens", "lane_steps"}`` of the window for one layer of
    ``kind``, from the loop's records; None without records, without the
    counters on them, or where no decode step ran."""
    recs = _loop.records(obs)
    if not recs or not hasattr(recs[0], COUNTER[kind]):
        return None
    out = {"ctx_tokens": _loop.total(recs, COUNTER[kind]),
           "lane_steps": _loop.total(recs, "lane_steps")}
    return out if out["lane_steps"] > 0 else None


def kernel_seconds(obs, kind):
    """Seconds of the traced stretch inside the kernel; None without
    a trace or without such an op (a program from before it)."""
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(KERNEL[kind])]
    return sum(hit) if hit else None


def time_share(obs, kind):
    s = kernel_seconds(obs, kind)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]


def roofline(obs, kind):
    """Percent: the least time the chip could take for the window's walks
    of ``kind``, per second of window, over the kernel's seconds per second
    of traced stretch."""
    d, seconds = delta(obs, kind), kernel_seconds(obs, kind)
    model = obs.get("config", {}).get("model", {})
    if d is None or seconds is None or not obs.get("peak") \
            or not layers(model, kind):
        return None
    fl, nbytes = cost(d["ctx_tokens"], d["lane_steps"], model, kind)
    w = _loop.seconds(obs)
    share, _bound = flops.roofline_share(
        fl / w, nbytes / w, seconds / obs["trace"]["window_s"], obs["peak"])
    return share
