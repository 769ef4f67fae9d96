"""Shared by the latent-attention readers: what the decode kernel of the
"mla" layers needs, from the program's counters, and which trace op it is.

The op: ``ops/attention.py``'s Pallas kernel carries its own name,
``jit__decode/latent_paged.<n>``, as ``ssm_step`` and jax's ``gmm`` do (it
is not ``branch_0_fun``, so ``paged_attn_time_share`` does not see it). The
query's way into the latent (``W_uk``), the result's way out (``W_uv``), the
rotation and the cache write are XLA ops without a name of their own and are
NOT in these seconds.

The counters (``obs["latent"]``, the engine's ``stats()["latent"]`` at the
window's two ends): ``ctx_tokens`` — the sum, over decode steps and their
live lanes, of the context the lane read (its own token included);
``lane_steps`` — live lanes summed over decode steps. A step calls the
kernel once for each "mla" layer.

Cost (the algorithm's, absorbed form, for one cached token read by one
stream in one layer, H heads, latent C, rotary R, two-byte pages): the
token's row is read once, ``(C + R) x 2`` bytes (1,152 at 512 + 64: the
pages keep the rotary key in a 128-lane row, 1,280 bytes, and the kernel
pays for that, not the roofline); every head takes its score against it and
adds it to its result, ``2 H (C + R) + 2 H C`` FLOPs (278,528 at 128 heads).
A lane-step and layer also reads the heads' query rows and writes their
results: ``H (C + R) x 2 + H C x 2`` bytes. Nothing else is counted, so the
share reads low where the kernel does more, never high.
"""
from benchmark import flops
from benchmark.layer_metrics._kernels import PROGRAM

KERNEL = PROGRAM["paged"] + "/latent_paged"     # jit__decode/latent_paged.<n>


def latent_layers(model):
    return sum(k == "mla" for k in model.get("layer_kinds") or ())


def cost(ctx_tokens, lane_steps, model, itemsize=2):
    """(flops, bytes) of the window's decode steps through every "mla"
    layer of ``model`` (a configuration file's ``model`` object)."""
    h, c, r = model["num_heads"], model["kv_rank"], model["rope_dim"]
    layers = latent_layers(model)
    fl = (2.0 * h * (c + r) + 2.0 * h * c) * ctx_tokens * layers
    nbytes = ((c + r) * itemsize * ctx_tokens
              + (h * (c + r) + h * c) * itemsize * lane_steps) * layers
    return fl, float(nbytes)


def delta(obs):
    """The window's counter deltas, or None where the program has no
    latent counters (or no decode step ran)."""
    lat = obs.get("latent")
    if not lat or not lat.get("before") or not lat.get("after"):
        return None
    out = {k: lat["after"][k] - lat["before"][k]
           for k in ("ctx_tokens", "lane_steps")}
    return out if out["lane_steps"] > 0 else None


def kernel_seconds(obs):
    """Seconds of the traced stretch inside the latent kernel; None without
    a trace or without such an op (a program from before it)."""
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(KERNEL)]
    return sum(hit) if hit else None


def roofline(obs):
    """Percent: the least time the chip could take for the window's latent
    attention, per second of window, over the kernel's seconds per second
    of traced stretch."""
    d, seconds = delta(obs), kernel_seconds(obs)
    model = obs.get("config", {}).get("model", {})
    if d is None or seconds is None or not obs.get("peak") \
            or not latent_layers(model):
        return None
    fl, nbytes = cost(d["ctx_tokens"], d["lane_steps"], model)
    w = obs["window_s"]
    share, _bound = flops.roofline_share(
        fl / w, nbytes / w, seconds / obs["trace"]["window_s"], obs["peak"])
    return share
