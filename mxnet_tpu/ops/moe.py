"""Routed expert FFN for inference: every chosen (token, expert) pair is
computed, nothing is dropped or capped.

``parallel/moe.py`` is the training layer (top-1 Switch routing, a capacity
factor that truncates an overfull expert). A server may not drop a token's
expert, and may not pay for all E experts where a token chose k of them, so
this is a second, smaller function:

    p      = softmax(router . x)  over all E experts, in float32
    top    = the k largest p, NOT renormalised
    out    = sum over e in top of p_e * down_e . (silu(gate_e . x) * (up_e . x))

(``route`` has a second scoring, DeepSeek-V3's sigmoid scores with a
bias-corrected, group-limited choice and renormalised, scaled weights; and
``moe_ffn(held=(first, count))`` computes one rank's share of an
expert-parallel layer: the router over all E, the pairs that fall on the
``count`` experts this rank holds, nothing of the others.)

The ``T x k`` pairs are sorted by expert (a counting sort: no comparison
sort on the device) and the three expert matmuls run as
GROUPED matmuls over the sorted rows (group e = the rows routed to expert
e): FLOPs are those of the chosen pairs, and an expert's weights are read
once per 128-row tile its group touches — once, at decode sizes. On the TPU
the grouped matmul is the Pallas ``gmm`` kernel that ships with jax
(``jax.experimental.pallas.ops.tpu.megablox``: group offsets ride in as
scalar prefetch and steer each grid step's weight DMA at its expert);
everywhere else it is ``lax.ragged_dot_general``. The platform is chosen at
LOWERING time like ``ops/attention.py``'s kernels, so a CPU run proves the
routing, the sort and the combine that the TPU runs. (``ragged_dot`` itself
is no choice on the TPU: XLA expands it to one dense masked matmul over all
E experts — E times the FLOPs and a ``(E, T*k, M)`` temporary; PERF.md
section 6, PR 26.)

The kernel's trace name is ``gmm.<n>`` (the jitted library function that
holds the ``pallas_call``), whatever wrapper it sits in.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import fp32_precision

__all__ = ["moe_ffn", "route"]

#: rows of the sorted pairs one grid step covers. A step computes all of
#: them for ONE expert (rows of other groups are masked), so the tile
#: should not be much larger than a group: T*k/E rows on average — 4 at
#: decode (bound by the weight read either way), 64 at a 512-token prefill
_TILE_M = 128
#: most columns of a weight tile: 1024 x 1024 bf16 = 2 MB a buffer
_TILE_KN = 1024

# contract lhs (R, K) with rhs (E, N, K) — the weights as the checkpoint
# stacks them, [out, in] — on K, one group of rows per expert
_RAGGED_NK = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (2,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])


def route(x, router, k, bias=None, kind="softmax", n_group=1, topk_group=1,
          scale=1.0):
    """Router weights of ``x`` (T, M) and each token's ``k`` experts:
    ``(weights (T, k) float32, experts (T, k) int32)``, over all E experts
    in float32.

    ``kind="softmax"``: the k largest of ``softmax(router . x)``; the
    chosen weights keep their values (not renormalised to sum to one).

    ``kind="sigmoid_group"`` (DeepSeek-V3's ``noaux_tc``): scores
    ``s = sigmoid(router . x)``; the CHOICE is made on ``s + bias`` (a
    buffer learned by load balancing), group-limited — the E experts are
    ``n_group`` groups, a group's score is the sum of its two largest
    ``s + bias``, the ``topk_group`` best groups stay (the others' entries
    read 0.0, as the published code fills them) and the k largest among
    them are the token's experts —, while the WEIGHTS are the chosen
    ``s`` themselves, renormalised to sum to one and times ``scale``."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x, router.T, preferred_element_type=jnp.float32,
                         precision=fp32_precision(x.dtype))
        if kind == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            weights, experts = lax.top_k(probs, k)
            return weights, experts.astype(jnp.int32)
        if kind != "sigmoid_group":
            raise ValueError("router kind %r" % (kind,))
        t, e = logits.shape
        scores = jax.nn.sigmoid(logits)
        choice = scores + bias.astype(jnp.float32)
        per = e // n_group
        group_score = jnp.sum(
            lax.top_k(choice.reshape(t, n_group, per), min(2, per))[0], -1)
        _, keep = lax.top_k(group_score, topk_group)     # (T, topk_group)
        kept = jnp.any(
            keep[:, :, None] == jnp.arange(n_group, dtype=keep.dtype), axis=1)
        choice = jnp.where(jnp.repeat(kept, per, axis=1), choice, 0.0)
        _, experts = lax.top_k(choice, k)
        weights = jnp.take_along_axis(scores, experts, axis=1)
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * scale, experts.astype(jnp.int32)


def _grouped_xla(rows, w, group_sizes):
    return lax.ragged_dot_general(
        rows, w, group_sizes, _RAGGED_NK, precision=fp32_precision(rows.dtype),
        preferred_element_type=jnp.float32)


def _grouped_pallas(rows, w, group_sizes, interpret=False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    r = rows.shape[0]
    pad = -r % _TILE_M
    if pad:
        # rows past sum(group_sizes) belong to no group: never visited
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, group_sizes, jnp.float32,
              tiling=(_TILE_M, min(_TILE_KN, w.shape[2]),
                      min(_TILE_KN, w.shape[1])),
              transpose_rhs=True, interpret=interpret)
    return out[:r]


def _grouped_matmul(rows, w, group_sizes):
    """``rows`` (R, K) sorted by group, ``w`` (E, N, K), ``group_sizes``
    (E,) summing to R -> (R, N) float32: row i times its group's w.T."""
    return lax.platform_dependent(rows, w, group_sizes,
                                  tpu=_grouped_pallas, default=_grouped_xla)


def moe_ffn(x, router, gate, up, down, k, valid=None, held=None,
            **route_args):
    """The routed gated FFN of one layer.

    x:      (T, M) tokens (after the layer's second norm)
    router: (E, M)
    gate:   (G, F, M)   up: (G, F, M)   down: (G, M, F)
    k:      experts per token (static)
    valid:  optional (T,) bool — padded lanes of a bucketed step are
            computed like any token (shapes are static) but are left out
            of the count
    held:   None (G = E: every expert is here), or ``(first, count)``: ONE
            RANK'S SHARE of an expert-parallel layer. The stacks hold the
            G = ``count`` experts ``first .. first + count - 1``; the
            router, its groups and the top-k run over all E; a chosen
            expert maps to its local group or to "not here", the pairs
            that are not here sort behind every group, where the grouped
            matmuls never visit them, and add nothing: the result is the
            partial sum the rank would send to the combine. No capacity
            and no bound: every pair that falls here is computed.
    route_args: :func:`route`'s (``bias``, ``kind``, ...)

    Returns ``(out (T, M) in x.dtype, tokens_per_expert (E,) int32)``;
    ``tokens_per_expert`` is the ROUTER's count over all E experts and
    sums to ``k`` times the number of valid tokens; a share computed the
    ``[first : first + count]`` slice of it.
    """
    t, m = x.shape
    e = router.shape[0]
    weights, experts = route(x, router, k, **route_args)
    with jax.named_scope("moe_experts"):
        flat = experts.reshape(t * k)
        chosen = flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None]
        if held is None:
            groups, onehot = e, chosen
        else:
            first, groups = held
            here = (flat >= first) & (flat < first + groups)
            # group `groups` is "not here": sorted last, computed never
            flat = jnp.where(here, flat - first, groups)
            onehot = flat[:, None] == jnp.arange(groups + 1,
                                                 dtype=jnp.int32)[None]
        sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
        group_sizes = sizes[:groups]
        if valid is None:
            counts = jnp.sum(chosen, axis=0, dtype=jnp.int32)
        else:
            counts = jnp.sum(chosen & jnp.repeat(valid, k)[:, None], axis=0,
                             dtype=jnp.int32)
        # a counting sort of the pairs by expert, stable in token order:
        # pair j goes to row dest[j] = its group's start + the pairs of
        # the same expert before it
        before = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        rank = jnp.take_along_axis(before, flat[:, None], axis=1)[:, 0]
        dest = jnp.take(jnp.cumsum(sizes) - sizes, flat) + rank
        order = jnp.zeros(t * k, jnp.int32).at[dest].set(
            jnp.arange(t * k, dtype=jnp.int32))          # row -> pair
        rows = jnp.take(x, order // k, axis=0)           # (T*k, M)
        grouped = functools.partial(_grouped_matmul, group_sizes=group_sizes)
        h = jax.nn.silu(grouped(rows, gate)) * grouped(rows, up)
        y = grouped(h.astype(x.dtype), down)             # (T*k, M) float32
        # back to token order: pair j of token i is pair i*k + j
        y = jnp.take(y, dest, axis=0) * weights.reshape(t * k, 1)
        if held is not None:
            # rows no group owns hold whatever the kernel left there
            y = jnp.where(here[:, None], y, 0.0)
        out = jnp.sum(y.reshape(t, k, m), axis=1).astype(x.dtype)
    return out, counts
