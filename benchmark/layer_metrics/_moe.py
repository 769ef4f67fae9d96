"""Shared by the expert-layer readers: what the routed FFN's grouped
matmuls need, from the program's counters, and which trace ops they are.

The counters (``obs["moe"]``, the engine's ``stats()["moe"]`` at the
window's two ends): ``pairs`` — (token, expert) pairs computed;
``experts_touched`` — experts with at least one live token, summed over
layer-steps; ``layer_steps``; ``tokens_per_expert`` — totals per layer and
expert.

Cost (the algorithm's, not the kernel's):

* FLOPs: a pair goes through gate, up and down, three matmuls of
  ``model_dim x ffn_dim``: ``6 x M x F`` per pair.
* Bytes: a touched expert's three matrices are read ONCE per layer-step,
  ``3 x M x F x itemsize``; a pair reads its input row and writes its output
  row, ``2 x M x itemsize``. The approximation: intermediates (gate and up
  outputs, the ``F``-wide product) are taken to stay on the chip, which the
  three separate kernels of today do not do; an expert whose group spans
  several row tiles of a prefill is read once per tile; and padded lanes of
  a bucketed step touch experts that no live token chose. All three make
  the kernels read MORE than is counted here, so the share reads low, never
  above 100 for these reasons.

The ops: the Pallas grouped matmul is jax's ``gmm``, and its custom call is
named after that function — ``jit__decode/gmm.<n>`` and
``jit__prefill/gmm.<n>`` — not ``branch_0_fun`` like the attention kernels.
Router, top-k, the sort and the gather are XLA fusions without a name of
their own and are NOT in these seconds.
"""
from benchmark import flops
from benchmark.layer_metrics._kernels import PROGRAM

KERNEL = "gmm"


def cost(pairs, experts_touched, model_dim, ffn_dim, itemsize=2):
    """(flops, bytes) the expert FFN needs for these counter deltas."""
    fl = 6.0 * pairs * model_dim * ffn_dim
    nbytes = (3.0 * experts_touched * model_dim * ffn_dim * itemsize
              + 2.0 * pairs * model_dim * itemsize)
    return fl, nbytes


def delta(obs):
    """The window's counter deltas, or None where the program has no
    expert counters (or nothing was routed)."""
    moe = obs.get("moe")
    if not moe or not moe.get("before") or not moe.get("after"):
        return None
    out = {k: moe["after"][k] - moe["before"][k]
           for k in ("pairs", "layer_steps", "experts_touched")}
    if out["layer_steps"] <= 0:
        return None
    out["tokens_per_expert"] = [
        [a - b for a, b in zip(row_a, row_b)]
        for row_a, row_b in zip(moe["after"]["tokens_per_expert"],
                                moe["before"]["tokens_per_expert"])]
    return out


def kernel_seconds(obs):
    """Seconds of the traced stretch inside the grouped-matmul kernels of
    the serving programs; None without a trace or without such an op."""
    tr = obs.get("trace")
    if not tr:
        return None
    hit = [s for name, s in tr["op_seconds"].items()
           if name.startswith(tuple(p + "/" + KERNEL
                                    for p in PROGRAM.values()))]
    return sum(hit) if hit else None


def roofline(obs):
    """Percent: the least time the chip could take for the window's expert
    work, per second of window, over the kernels' seconds per second of
    traced stretch."""
    d, seconds = delta(obs), kernel_seconds(obs)
    if d is None or seconds is None or not obs.get("peak"):
        return None
    m = obs["config"]["model"]
    fl, nbytes = cost(d["pairs"], d["experts_touched"], m["model_dim"],
                      m["ffn_dim"])
    w = obs["window_s"]
    share, _bound = flops.roofline_share(
        fl / w, nbytes / w, seconds / obs["trace"]["window_s"], obs["peak"])
    return share
