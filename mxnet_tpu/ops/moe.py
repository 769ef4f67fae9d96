"""Routed expert FFN for inference: every chosen (token, expert) pair is
computed, nothing is dropped or capped.

``parallel/moe.py`` is the training layer (top-1 Switch routing, a capacity
factor that truncates an overfull expert). A server may not drop a token's
expert, and may not pay for all E experts where a token chose k of them, so
this is a second, smaller function:

    p      = softmax(router . x)  over all E experts, in float32
    top    = the k largest p, NOT renormalised
    out    = sum over e in top of p_e * down_e . (silu(gate_e . x) * (up_e . x))

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


def route(x, router, k):
    """Router probabilities of ``x`` (T, M) and each token's ``k`` experts:
    ``(weights (T, k) float32, experts (T, k) int32)``. The softmax runs
    over all E experts in float32 and the chosen weights keep their
    values: they are not renormalised to sum to one."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x, router.T, preferred_element_type=jnp.float32,
                         precision=fp32_precision(x.dtype))
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(probs, k)
    return weights, experts.astype(jnp.int32)


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


def moe_ffn(x, router, gate, up, down, k, valid=None):
    """The routed gated FFN of one layer.

    x:      (T, M) tokens (after the layer's second norm)
    router: (E, M)
    gate:   (E, F, M)   up: (E, F, M)   down: (E, M, F)
    k:      experts per token (static)
    valid:  optional (T,) bool — padded lanes of a bucketed step are
            computed like any token (shapes are static) but are left out
            of the count

    Returns ``(out (T, M) in x.dtype, tokens_per_expert (E,) int32)``;
    ``tokens_per_expert`` sums to ``k`` times the number of valid tokens.
    """
    t, m = x.shape
    e = router.shape[0]
    weights, experts = route(x, router, k)
    with jax.named_scope("moe_experts"):
        flat = experts.reshape(t * k)
        onehot = flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None]
        group_sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
        if valid is None:
            counts = group_sizes
        else:
            counts = jnp.sum(onehot & jnp.repeat(valid, k)[:, None], axis=0,
                             dtype=jnp.int32)
        # a counting sort of the pairs by expert, stable in token order:
        # pair j goes to row dest[j] = its group's start + the pairs of
        # the same expert before it
        before = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        rank = jnp.take_along_axis(before, flat[:, None], axis=1)[:, 0]
        dest = jnp.take(jnp.cumsum(group_sizes) - group_sizes, flat) + rank
        order = jnp.zeros(t * k, jnp.int32).at[dest].set(
            jnp.arange(t * k, dtype=jnp.int32))          # row -> pair
        rows = jnp.take(x, order // k, axis=0)           # (T*k, M)
        grouped = functools.partial(_grouped_matmul, group_sizes=group_sizes)
        h = jax.nn.silu(grouped(rows, gate)) * grouped(rows, up)
        y = grouped(h.astype(x.dtype), down)             # (T*k, M) float32
        # back to token order: pair j of token i is pair i*k + j
        y = jnp.take(y, dest, axis=0) * weights.reshape(t * k, 1)
        out = jnp.sum(y.reshape(t, k, m), axis=1).astype(x.dtype)
    return out, counts
