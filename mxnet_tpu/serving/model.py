"""Functional Transformer-LM forward for serving.

The serving engine cannot run the symbol executors: prefill needs the K/V
projections OUT of the graph (to scatter into the shared block pool) and
decode needs attention THROUGH per-request block tables. This module is the
functional twin of ``models/transformer_lm.py`` — same parameter names, same
primitive-for-primitive numerics (LayerNorm composed from mean/square/sqrt
with the same 1e-5 epsilon, the same fused-qkv einsums at
``fp32_precision``, ``flash_attention`` for prefill exactly as the training
block uses it) — so a trained checkpoint's ``arg_params`` drop straight in
and the paged decode reproduces the contiguous cached decoder to float
tolerance (tests_tpu/test_serving.py pins it at <1e-5 for fp32).

The step functions are PURE (params and pages in, logits and pages out):
the engine wraps them in ``compileobs.jit`` with the pool pages donated, so
each shape bucket compiles exactly once. The pages are the pool's
``(L, N, bs, G, W)`` arrays in whatever row format they were built
(``PageSpec.lane_dense``; ``(H, D)`` rows are the r = 1 case): new K/V
rows are reshaped to ``(G, W)`` before the scatter and attention reads the
WHOLE pool at a static layer index, so that a lane-dense pool is scattered
into and read where it lies — ``k_pages[i]`` is a copy of a layer, and a
pool whose rows are narrower than the 128 lanes is copied whole, in and out
of every program (tests/test_aot_tpu_compile.py guards both).

Padded-lane safety contract: bucketed steps carry dead lanes (padded batch
rows, padded prompt tail). Dead lanes write through the block table's
TRASH entries (block 0) and read under a context-length mask that pins
their scores to exp(-1e30)=0 — garbage can neither corrupt a live block
nor leak into a live row. An out-of-range decode position (>= max_len) is
routed to the trash block and its lane's outputs poisoned (token -1,
logits NaN): the paged path upholds the same graph-level overflow contract
as ``_contrib_CachedMultiHeadAttention``.
"""
import collections

import numpy as np

from ..ops.attention import (flash_attention, flash_attention_gqa,
                             latent_paged, paged_attention_multi)
from ..ops.kda import kda_chunk, kda_step
from ..ops.moe import moe_ffn
from ..ops.registry import fp32_precision
from ..ops.ssm import ssm_scan, ssm_step
from .kv_cache import PageSpec

#: what :meth:`ModelConfig.cache_specs` answers: the full-length pool's
#: spec and the window pool's
CacheSpecs = collections.namedtuple("CacheSpecs", "full window")

#: parameter init scale matching models/transformer_lm.py's Normal(0.02)
#: pos-embed init; used by random_params for self-contained serving runs
_INIT_SCALE = 0.02


class ModelConfig:
    """Static Transformer-LM shape config (hashable: feeds compileobs
    graph keys). The first six fields are the GPT-2 block's; the rest say
    which block it is, structurally, with GPT-2's values as defaults:

    norm            "layer" (mean/variance, gamma and beta) or "rms"
                    (statistics in float32, gamma only)
    pos             "learned" (a position table of ``max_len`` rows added
                    to the embedding) or "rope" (rotate-half rotary
                    position on q and k, K cached rotated)
    rope_theta      the rotary base
    qk_norm         RMSNorm on the whole q and k projections, before the
                    split into heads
    head_dim        width of one head (default ``model_dim // num_heads``)
    num_experts     0 = a dense ReLU FFN of ``ffn_dim``; E > 0 = E
                    SiLU-gated experts of ``ffn_dim`` behind a router
    experts_per_tok experts a token is sent to (nothing is dropped)
    bias            biases on the FFN and the head (experts have none)

    A model whose layers are not all that one block names each layer's
    KIND in ``layer_kinds`` (None = every layer ``"attn"``, the block
    above); the norm / residual / FFN skeleton stays the one body and only
    the mixer differs:

    ``"attn"``   the block above: its own K/V, read from the stream's start
                 (the one-block model's only kind; ``layer_kinds`` cannot
                 name it: the step programs of a model with kinds have no
                 path for it)
    ``"mamba"``  a Mamba-1 selective state-space layer (``ssm_*`` below):
                 per-stream conv tail and float32 state, no K/V. The last
                 one before the first ``"gmu"`` also hands its scan output
                 of the same token to those layers
    ``"swa"``    differential attention over a sliding window of ``window``
                 keys (the query's own included), its own K/V
    ``"full"``   the same with no window: its K/V is a full-length cache
    ``"cross"``  a query projection only; reads the K/V of the last
                 ``"full"`` layer before it and writes none
    ``"gmu"``    a gated memory unit: ``W_out (silu(W_in h) * m)``, no state
    ``"mla"``    multi-head latent attention (DeepSeek-V3's): queries
                 through a normalised ``q_rank`` bottleneck, keys and
                 values expanded from a normalised ``kv_rank`` latent; a
                 head is ``head_dim`` lanes without position plus
                 ``rope_dim`` rotary lanes (ONE rotary key a token, shared
                 by the heads), its value ``v_dim`` wide. The cache holds
                 the latent and the rotated key, not per-head K and V;
                 prefill attends over expanded heads, decode over the
                 absorbed form. Needs ``pos="rope"``; takes no other
                 attention kind beside it

    num_kv_heads    K/V heads (default ``num_heads``); the ``swa`` / ``full``
                    / ``cross`` kinds are DIFFERENTIAL attention: adjacent
                    query heads pair, adjacent K/V heads pair, a query pair
                    reads K/V pair ``p * kv_pairs // q_pairs`` and a head's
                    value is its K/V pair's two heads side by side
    window          keys a ``"swa"`` layer's query sees
    attn_bias       biases on those kinds' q/k/v and output projections
    ffn_gated       the dense FFN is ``W2 (silu(g) * u)``, ``[g, u] = W1 h``
    tie_embed       the head is the embedding (no ``lm_head_weight``)
    ssm_state, ssm_conv, ssm_expand, ssm_dt_rank
                    Mamba's ``d_state``, ``d_conv``, ``d_inner / model_dim``
                    and ``dt_rank`` (None: ``ceil(model_dim / 16)``)
    pos             may then also be "none" (no position anywhere)
    q_rank, kv_rank, rope_dim, v_dim
                    the "mla" kind's widths (above)
    rope_yarn       None, or YaRN's ``(factor, original_len, beta_fast,
                    beta_slow, mscale, mscale_all_dim)`` on the rotary lanes
    norm_eps        the norms' epsilon

    The FFN may differ by layer too, and a layer with experts may hold a
    share of them:

    first_dense     leading layers whose FFN is the dense one (of
                    ``dense_ffn_dim``) in a model with experts
    dense_ffn_dim   that FFN's width (default ``ffn_dim``, which stays the
                    width of ONE expert)
    shared_experts  experts every token goes through beside its routed
                    ones (one gated FFN of ``shared_experts x ffn_dim``)
    router          "softmax" (top-k of a softmax, not renormalised) or
                    "sigmoid_group" (``ops.moe.route``) with ``n_group``,
                    ``topk_group``, ``route_scale``
    experts_held    None, or ``(first, count)``: this engine is ONE RANK
                    of an expert-parallel layout and holds ``count`` of
                    the ``num_experts`` the router chooses among

    A one-block model may also be a stack that every token goes through
    SEVERAL times (a looped language model), and may norm a sub-layer's
    output as well as its input; ``ffn_gated`` and ``norm_eps`` above are
    a one-block model's to set too:

    loop_steps      passes through the stack (1 = once). Every pass uses the
                    same weights, closes with the final norm, hands its result
                    to the next as input, and has a K/V cache OF ITS OWN:
                    ``cache_layers = loop_steps x num_layers``. The head reads
                    the last pass
    post_norm       a second norm on each sub-layer's OUTPUT, before the
                    residual add (``layer%d_ln1_post_*``, ``_ln2_post_*``)
    early_exit_threshold
                    1.0: no token leaves before the last pass (the exit
                    gate's two parameters are loaded and nothing reads them);
                    anything less is refused

    The "swa" and "full" kinds say WHERE a layer's K/V lives and how far a
    query reads; the head's form is a second property:

    attn_form       "diff" (the differential attention above) or "gqa": plain
                    softmax attention, query head ``j`` reading K/V head
                    ``j // (num_heads / kv heads)``. A "gqa" model's kinds
                    are "swa" and "full" alone, and it may set
    swa_kv_heads    K/V heads of a "swa" layer (default ``num_kv_heads``,
                    which stays a "full" layer's): the two pools' rows differ
    pos="rope"      rotate-half rotary position on the FIRST ``rope_dim``
                    lanes of every q and k head (0: the whole head), the
                    others pass through; base ``rope_theta`` in a "full" layer,
    swa_rope_theta  in a "swa" layer (default ``rope_theta``)
    v_dim           a value's width, which need not be the key's ``head_dim``
    swa_sink        a learned scalar a query head in the "swa" layers
                    (``layer%d_attn_sink``) that joins the softmax's
                    denominator and no numerator
    value_scale     the heads' results are multiplied by it

    attn_gate       the heads' results are multiplied elementwise by
                    ``sigmoid(W_gate h)`` (``layer%d_attn_gate_weight``)
                    before the output projection

    A "gqa" model may also have layers of LINEAR attention beside its "full"
    (and "swa") ones:

    ``"kda"``    a gated delta-rule layer (Kimi Delta Attention,
                 ``ops/kda.py``): q, k and v each through a causal depthwise
                 conv of ``kda_conv`` taps and SiLU, ``kda_heads`` heads of
                 ``kda_head_dim`` keys (q and k L2-normalised a head) and as
                 many values; a log decay a key channel through a
                 rank-``kda_head_dim`` pair, ``-exp(A_log) softplus(. +
                 dt_bias)``; a step size ``beta = sigmoid(W_b h)`` a head,
                 doubled where ``kda_neg_eigval``; per stream a conv tail of
                 the three projections and a float32 state ``(heads, keys,
                 values)`` in the stream's slot, no K/V; the heads' results
                 RMS-normed a head and gated by a sigmoid through a second
                 pair of that rank

    ``max_len`` bounds every stream's total length (the position table's
    rows, or the positions the rotary model was trained for)."""

    __slots__ = ("vocab_size", "num_layers", "model_dim", "num_heads",
                 "ffn_dim", "max_len", "norm", "pos", "rope_theta",
                 "qk_norm", "head_dim", "num_experts", "experts_per_tok",
                 "bias", "layer_kinds", "num_kv_heads", "window",
                 "attn_bias", "ffn_gated", "tie_embed", "ssm_state",
                 "ssm_conv", "ssm_expand", "ssm_dt_rank", "q_rank", "kv_rank",
                 "rope_dim", "v_dim", "rope_yarn", "norm_eps", "first_dense",
                 "dense_ffn_dim", "shared_experts", "router", "n_group",
                 "topk_group", "route_scale", "experts_held", "loop_steps",
                 "post_norm", "early_exit_threshold", "attn_form",
                 "swa_kv_heads", "swa_rope_theta", "swa_sink", "value_scale",
                 "attn_gate", "kda_heads", "kda_head_dim", "kda_conv",
                 "kda_neg_eigval")
    #: the fields of one-block models: their ``key()`` is these alone, so
    #: that the programs' cache keys are what they were before ``layer_kinds``
    _BLOCK_FIELDS = 14
    #: a model with kinds' key: the fields there were before ``loop_steps``
    _KIND_FIELDS = 38
    #: what a one-block model may set beyond its fourteen; one that does
    #: has them in its key
    _BLOCK_EXTRAS = (("ffn_gated", False), ("norm_eps", 1e-5),
                     ("loop_steps", 1), ("post_norm", False))
    #: a model with kinds whose ``attn_form`` is not "diff" has these in its
    #: key behind the thirty-eight
    _FORM_FIELDS = ("attn_form", "swa_kv_heads", "swa_rope_theta",
                    "swa_sink", "value_scale")
    #: a "gqa" model with "kda" layers or an output gate has these behind
    #: those
    _LINEAR_FIELDS = ("attn_gate", "kda_heads", "kda_head_dim", "kda_conv",
                      "kda_neg_eigval")
    #: what ``layer_kinds`` may name
    KINDS = ("mamba", "swa", "full", "cross", "gmu", "mla", "kda")

    def __init__(self, vocab_size=32000, num_layers=4, model_dim=256,
                 num_heads=4, ffn_dim=1024, max_len=128, norm="layer",
                 pos="learned", rope_theta=10000.0, qk_norm=False,
                 head_dim=None, num_experts=0, experts_per_tok=0, bias=True,
                 layer_kinds=None, num_kv_heads=None, window=0,
                 attn_bias=False, ffn_gated=False, tie_embed=False,
                 ssm_state=16, ssm_conv=4, ssm_expand=2, ssm_dt_rank=None,
                 q_rank=0, kv_rank=0, rope_dim=0, v_dim=None, rope_yarn=None,
                 norm_eps=1e-5, first_dense=0, dense_ffn_dim=None,
                 shared_experts=0, router="softmax", n_group=1, topk_group=1,
                 route_scale=1.0, experts_held=None, loop_steps=1,
                 post_norm=False, early_exit_threshold=1.0, attn_form="diff",
                 swa_kv_heads=None, swa_rope_theta=None, swa_sink=False,
                 value_scale=1.0, attn_gate=False, kda_heads=None,
                 kda_head_dim=None, kda_conv=4, kda_neg_eigval=False):
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.model_dim = int(model_dim)
        self.num_heads = int(num_heads)
        self.ffn_dim = int(ffn_dim)
        self.max_len = int(max_len)
        self.norm = str(norm)
        self.pos = str(pos)
        self.rope_theta = float(rope_theta)
        self.qk_norm = bool(qk_norm)
        if head_dim is None and self.model_dim % self.num_heads:
            raise ValueError("model_dim must divide by num_heads")
        self.head_dim = int(head_dim if head_dim is not None
                            else self.model_dim // self.num_heads)
        self.num_experts = int(num_experts)
        self.experts_per_tok = int(experts_per_tok)
        self.bias = bool(bias)
        self.layer_kinds = (None if layer_kinds is None
                            else tuple(str(k) for k in layer_kinds))
        self.num_kv_heads = int(num_kv_heads if num_kv_heads is not None
                                else self.num_heads)
        self.window = int(window)
        self.attn_bias = bool(attn_bias)
        self.ffn_gated = bool(ffn_gated)
        self.tie_embed = bool(tie_embed)
        self.ssm_state = int(ssm_state)
        self.ssm_conv = int(ssm_conv)
        self.ssm_expand = int(ssm_expand)
        self.ssm_dt_rank = int(ssm_dt_rank if ssm_dt_rank is not None
                               else -(-self.model_dim // 16))
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.rope_dim = int(rope_dim)
        self.v_dim = int(v_dim if v_dim is not None else self.head_dim)
        self.rope_yarn = (None if rope_yarn is None
                          else tuple(float(v) for v in rope_yarn))
        self.norm_eps = float(norm_eps)
        self.first_dense = int(first_dense)
        self.dense_ffn_dim = int(dense_ffn_dim if dense_ffn_dim is not None
                                 else self.ffn_dim)
        self.shared_experts = int(shared_experts)
        self.router = str(router)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.route_scale = float(route_scale)
        self.experts_held = (None if experts_held is None
                             else tuple(int(v) for v in experts_held))
        self.loop_steps = int(loop_steps)
        self.post_norm = bool(post_norm)
        self.early_exit_threshold = float(early_exit_threshold)
        self.attn_form = str(attn_form)
        self.swa_kv_heads = int(swa_kv_heads if swa_kv_heads is not None
                                else self.num_kv_heads)
        self.swa_rope_theta = float(swa_rope_theta if swa_rope_theta
                                    is not None else self.rope_theta)
        self.swa_sink = bool(swa_sink)
        self.value_scale = float(value_scale)
        self.attn_gate = bool(attn_gate)
        self.kda_heads = int(kda_heads if kda_heads is not None
                             else self.num_heads)
        self.kda_head_dim = int(kda_head_dim if kda_head_dim is not None
                                else self.head_dim)
        self.kda_conv = int(kda_conv)
        self.kda_neg_eigval = bool(kda_neg_eigval)
        if self.norm not in ("layer", "rms"):
            raise ValueError("norm must be 'layer' or 'rms', not %r" % norm)
        if self.pos not in ("learned", "rope", "none"):
            raise ValueError("pos must be 'learned', 'rope' or 'none', not %r"
                             % pos)
        if self.pos == "rope" and self.head_dim % 2:
            raise ValueError("rotary position needs an even head_dim")
        if self.attn_form not in ("diff", "gqa"):
            raise ValueError("attn_form must be 'diff' or 'gqa', not %r"
                             % attn_form)
        if self.num_experts and not (
                1 <= self.experts_per_tok <= self.num_experts):
            raise ValueError("experts_per_tok must be in 1..num_experts")
        self._check_kinds()
        self._check_ffn()
        self._check_loop()

    def _check_loop(self):
        if self.loop_steps < 1:
            raise ValueError("loop_steps must be >= 1")
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                "early_exit_threshold %g: a token that leaves the stack "
                "before its last pass needs what the step programs do not "
                "have yet (lanes of one batch leaving at different passes, "
                "the exit gate evaluated); only 1.0, every pass for every "
                "token, is served" % self.early_exit_threshold)
        if (self.loop_steps > 1 or self.post_norm) and self.hybrid:
            raise ValueError("loop_steps and post_norm belong to a one-block "
                             "model, not to one with layer_kinds")
        if self.loop_steps > 1 and self.num_experts:
            raise ValueError("a looped stack takes no routed experts (their "
                             "load is booked a layer, not a layer and pass)")

    def _check_ffn(self):
        if self.router not in ("softmax", "sigmoid_group"):
            raise ValueError("router must be 'softmax' or 'sigmoid_group', "
                             "not %r" % self.router)
        if not self.num_experts:
            if self.first_dense or self.shared_experts or self.experts_held:
                raise ValueError("first_dense, shared_experts and "
                                 "experts_held belong to a model with experts")
            return
        if not 0 <= self.first_dense < self.num_layers:
            raise ValueError("first_dense must leave a layer with experts")
        if self.router == "sigmoid_group" and (
                self.num_experts % self.n_group
                or not 1 <= self.topk_group <= self.n_group
                or self.experts_per_tok
                > self.topk_group * (self.num_experts // self.n_group)):
            raise ValueError("%d experts do not make %d groups of which %d "
                             "hold %d experts a token"
                             % (self.num_experts, self.n_group,
                                self.topk_group, self.experts_per_tok))
        if self.experts_held is not None:
            first, count = self.experts_held
            if count < 1 or first < 0 or first + count > self.num_experts:
                raise ValueError("experts_held %r is no part of %d experts"
                                 % (self.experts_held, self.num_experts))

    def _check_kinds(self):
        kinds = self.kinds()
        by_layer = (self.first_dense or self.shared_experts
                    or self.experts_held or self.router != "softmax"
                    or self.rope_yarn)
        if self.layer_kinds is None:
            if (self.num_kv_heads != self.num_heads or self.pos == "none"
                    or self.tie_embed or self.attn_bias or by_layer
                    or self.gqa or self.attn_gate):
                raise ValueError(
                    "num_kv_heads, pos='none', tie_embed, attn_bias, "
                    "rope_yarn, attn_form, attn_gate and an FFN that differs "
                    "by layer belong to a model with layer_kinds")
            return
        if len(kinds) != self.num_layers or set(kinds) - set(self.KINDS):
            raise ValueError("layer_kinds must name each of the %d layers "
                             "one of %s, not %r"
                             % (self.num_layers, self.KINDS, kinds))
        if self.qk_norm:
            raise ValueError("a model with layer_kinds takes no QK-norm")
        if "mla" in kinds:
            if set(kinds) & {"swa", "full", "cross"}:
                raise ValueError("'mla' layers share the full pool with no "
                                 "other attention kind")
            if self.pos != "rope" or self.rope_dim < 2 or self.rope_dim % 2 \
                    or self.q_rank < 1 or self.kv_rank < 1:
                raise ValueError("'mla' layers need pos='rope', an even "
                                 "rope_dim, q_rank and kv_rank")
        elif self.gqa:
            self._check_gqa(kinds)
        elif self.pos == "rope" or self.rope_yarn:
            raise ValueError("of the layer kinds only 'mla' has rotary "
                             "position (and 'swa' / 'full' where attn_form "
                             "is 'gqa')")
        if not self.gqa and ("kda" in kinds or self.attn_gate):
            raise ValueError("'kda' layers and attn_gate belong to a model "
                             "whose attn_form is 'gqa'")
        if set(kinds) & {"swa", "full", "cross"} and not self.gqa:
            if self.num_heads % 2 or self.num_kv_heads % 2 or \
                    (self.num_heads // 2) % (self.num_kv_heads // 2):
                raise ValueError(
                    "differential attention pairs adjacent heads: %d query "
                    "and %d K/V heads do not pair up"
                    % (self.num_heads, self.num_kv_heads))
        if "swa" in kinds and self.window < 1:
            raise ValueError("'swa' layers need window >= 1")
        for i, kind in enumerate(kinds):
            if kind == "cross" and "full" not in kinds[:i]:
                raise ValueError("layer %d is 'cross' with no 'full' layer "
                                 "before it" % i)
            if kind == "gmu" and "mamba" not in kinds[:i]:
                raise ValueError("layer %d is 'gmu' with no 'mamba' layer "
                                 "before it" % i)

    def _check_gqa(self, kinds):
        if set(kinds) - {"swa", "full", "kda"}:
            raise ValueError("attn_form 'gqa' takes 'swa', 'full' and 'kda' "
                             "layers alone, not %r" % (kinds,))
        if "kda" in kinds and (self.kda_conv < 2 or min(
                self.kda_heads, self.kda_head_dim) < 1):
            raise ValueError("'kda' layers need kda_conv >= 2 taps and "
                             "positive kda_heads and kda_head_dim")
        for kind in set(kinds) - {"kda"}:
            if self.num_heads % self.kv_heads_of(kind):
                raise ValueError(
                    "%d query heads do not share %d K/V heads of a %r layer"
                    % (self.num_heads, self.kv_heads_of(kind), kind))
        if self.attn_bias or self.rope_yarn:
            raise ValueError("attn_form 'gqa' takes no attn_bias and no "
                             "rope_yarn")
        if self.pos == "learned" or self.rope_dim % 2 \
                or not 0 <= self.rope_dim <= self.head_dim:
            raise ValueError("attn_form 'gqa' takes pos 'rope' or 'none' and "
                             "an even rope_dim of at most head_dim")

    # ---- what the layers' kinds imply (static, python) ------------------
    def kinds(self):
        return self.layer_kinds or ("attn",) * self.num_layers

    def layers_of(self, *kinds):
        """Indices of the layers of these kinds, in order."""
        return [i for i, k in enumerate(self.kinds()) if k in kinds]

    @property
    def hybrid(self):
        """A model of several layer kinds: its step programs take the
        window pool's pages and the state slots beside the full pool."""
        return self.layer_kinds is not None

    @property
    def gqa(self):
        """The "swa" and "full" layers are plain grouped-query softmax
        attention (``attn_form``)."""
        return self.attn_form == "gqa"

    def kv_heads_of(self, kind):
        """K/V heads of a layer of ``kind``."""
        return self.swa_kv_heads if kind == "swa" else self.num_kv_heads

    def rope_theta_of(self, kind):
        return self.swa_rope_theta if kind == "swa" else self.rope_theta

    @property
    def stateful(self):
        """Per-stream state that no block-aligned prefix determines (a
        recurrent state, a window that has slid): no prefix sharing, no
        roll-back of a speculated window."""
        return bool(self.layers_of("mamba", "swa", "kda"))

    @property
    def linear(self):
        """The model has "kda" layers: a matrix state a head in the stream's
        slot, rewritten every step."""
        return bool(self.layers_of("kda"))

    @property
    def kda_channels(self):
        """Channels of a "kda" layer's conv: q, k and v side by side."""
        return 3 * self.kda_heads * self.kda_head_dim

    def slot_shapes(self):
        """``(conv_width, state_shape)`` of one layer's part of a stream's
        state slot (``StateSlots``): the conv tail's values, in the
        activations' type, and the float32 state — a "mamba" layer's ``(N,
        Dn)`` (states along the sublanes, channels along the lanes), a "kda"
        layer's ``(heads, keys, values)`` (a head is whole ``(8, 128)`` tiles,
        the values along the lanes). A model with neither keeps the "mamba"
        shapes for its two-slot stand-in."""
        if self.linear:
            return ((self.kda_conv - 1) * self.kda_channels,
                    (self.kda_heads, self.kda_head_dim, self.kda_head_dim))
        return ((self.ssm_conv - 1) * self.d_inner,
                (self.ssm_state, self.d_inner))

    @property
    def latent(self):
        """The cache holds "mla" layers' latents: a block's contents are no
        per-head K and V, and no program extends over them yet (no prefix
        sharing, no verify pass)."""
        return bool(self.layers_of("mla"))

    @property
    def expert_layers(self):
        """How many layers have experts (those behind ``first_dense``)."""
        return self.num_layers - self.first_dense if self.num_experts else 0

    @property
    def experts_here(self):
        """``(first, count)`` of the experts this engine holds."""
        return self.experts_held or (0, self.num_experts)

    @property
    def d_inner(self):
        return self.ssm_expand * self.model_dim

    @property
    def memory_layer(self):
        """The 'mamba' layer whose scan output the 'gmu' layers gate."""
        gmu = self.layers_of("gmu")
        return max(i for i in self.layers_of("mamba") if i < gmu[0]) \
            if gmu else None

    def cache_specs(self):
        """``(full, window)``: the :class:`~.kv_cache.PageSpec` of the
        full-length pool and of the window pool — the ONE place a model
        family decides its page format (the pools, the step programs and
        the engine read the specs). A one-block model has no window pool
        (``None``); a model with ``layer_kinds`` but no "swa" (or no
        "full" / "mla") layer gets a one-layer stand-in of the same rows,
        so that the step programs have one signature.

        * one block: the lane-dense rows, ``loop_steps`` parts;
        * ``attn_form`` "gqa": a K/V head of the pool's layer kind is a
          row, K's ``head_dim`` and V's ``v_dim`` lanes each padded to
          whole 128-lane tiles (192 -> 256: a head-major slab ``(bs, W)``
          is then whole tiles for the copy and the MXU, which pads 192 to
          256 itself; a 192-lane minor dimension is padded to 256 in HBM's
          tiled layout anyway), V's row narrower than K's;
        * "mla" layers, the LATENT format: ``k_pages`` hold a token's
          normalised latent, one ``kv_rank``-wide row; ``v_pages`` its
          rotated key, ``rope_dim`` lanes padded to whole tiles (a 64-lane
          row is half a tile: the pool's layout would not be row-major, PR
          25; two tokens a row would save a tenth of the cache's bytes and
          make every decode write half a row);
        * differential attention: a K/V pair is one row (``[v1, v2]`` is
          the row, ``k1`` and ``k2`` its halves), in the order its tiles
          ask for (``PageSpec.tiled``).

        The latent's one row a token is head-major at any width (the block
        is the ``(bs, W)`` slab either way); so are "gqa"'s pools, whose K
        and V rows differ."""
        if not self.hybrid:
            return CacheSpecs(PageSpec.lane_dense(
                self.num_layers, self.num_heads, self.head_dim,
                self.loop_steps), None)

        def whole_tiles(lanes):
            return -(-lanes // 128) * 128

        def spec(layers, kind):
            layers, heads = max(layers, 1), self.kv_heads_of(kind)
            if self.gqa:
                return PageSpec(layers, (heads, whole_tiles(self.head_dim)),
                                (heads, whole_tiles(self.v_dim)), True)
            if self.latent:
                return PageSpec(layers, (1, self.kv_rank),
                                (1, whole_tiles(self.rope_dim)), True)
            if set(self.kinds()) & {"swa", "full", "cross"}:
                return PageSpec.tiled(layers, (self.num_kv_heads // 2,
                                               2 * self.head_dim))
            # no layer reads a pool: stand-ins of the lane-dense rows
            return PageSpec.tiled(layers, PageSpec.lane_dense(
                layers, self.num_kv_heads, self.head_dim).k_rows)

        return CacheSpecs(spec(len(self.layers_of("full", "mla")), "full"),
                          spec(len(self.layers_of("swa")), "swa"))

    def key(self):
        """What the programs are a function of. A one-block model's key is
        its first fourteen fields, as before there were others, and what
        it sets of ``_BLOCK_EXTRAS`` behind them if it sets any; a model
        with kinds' is the thirty-eight it always was, with ``_FORM_FIELDS``
        behind them where ``attn_form`` is not "diff" and
        ``_LINEAR_FIELDS`` behind those where it has "kda" layers or an
        output gate."""
        names = ModelConfig.__slots__[:self._KIND_FIELDS]
        if not self.hybrid:
            names = names[:self._BLOCK_FIELDS]
            if any(getattr(self, k) != v for k, v in self._BLOCK_EXTRAS):
                names += tuple(k for k, _v in self._BLOCK_EXTRAS)
        elif self.gqa:
            names += self._FORM_FIELDS
            if self.linear or self.attn_gate:
                names += self._LINEAR_FIELDS
        return tuple(getattr(self, k) for k in names)

    def _slot_names(self):
        # walk the whole MRO: on a subclass (ServingConfig) bare
        # self.__slots__ resolves to the subclass's slots only, silently
        # dropping the model-shape fields from repr/as_dict
        names = []
        for klass in reversed(type(self).__mro__):
            names.extend(getattr(klass, "__slots__", ()))
        return names

    def as_dict(self):
        return {k: getattr(self, k) for k in self._slot_names()}

    def __repr__(self):
        # %r, not %d: subclass slots hold non-int values (kv_dtype) and
        # this repr feeds the as_device_params diagnostics — it must
        # never itself raise
        return "%s(%s)" % (type(self).__name__, ", ".join(
            "%s=%r" % (k, getattr(self, k)) for k in self._slot_names()))


def param_shapes(cfg):
    """Name -> shape for every weight the serving forward consumes. For
    the GPT-2 block these are exactly the training graph's ``arg_dict``
    names (minus data/label); the other blocks add and drop names by the
    config's structural fields (expert weights stacked per layer,
    ``[expert, out, in]`` as a checkpoint has them)."""
    m, f, v = cfg.model_dim, cfg.ffn_dim, cfg.vocab_size
    hm = cfg.num_heads * cfg.head_dim
    shapes = {"embed_weight": (v, m), "final_ln_gamma": (1, 1, m)}
    if not cfg.tie_embed:
        shapes["lm_head_weight"] = (v, m)
    if cfg.pos == "learned":
        shapes["pos_embed_weight"] = (1, cfg.max_len, m)
    if cfg.norm == "layer":
        shapes["final_ln_beta"] = (1, 1, m)
    if cfg.bias:
        shapes["lm_head_bias"] = (v,)
    if cfg.loop_steps > 1:      # loaded; read by no program at threshold 1
        shapes.update({"early_exit_gate_weight": (1, m),
                       "early_exit_gate_bias": (1,)})
    norms = ("_ln1", "_ln2") + (("_ln1_post", "_ln2_post")
                                if cfg.post_norm else ())
    for i, kind in enumerate(cfg.kinds()):
        p = "layer%d" % i
        shapes.update({p + n + "_gamma": (1, 1, m) for n in norms})
        if kind == "attn":
            shapes.update({p + "_attn_in_weight": (3 * hm, m),
                           p + "_attn_out_weight": (m, hm)})
        else:
            shapes.update(_mixer_shapes(cfg, kind, p))
        if cfg.norm == "layer":
            shapes.update({p + n + "_beta": (1, 1, m) for n in norms})
        if cfg.qk_norm:
            shapes.update({p + "_q_norm_gamma": (hm,),
                           p + "_k_norm_gamma": (hm,)})
        if cfg.num_experts and i >= cfg.first_dense:
            e, held = cfg.num_experts, cfg.experts_here[1]
            shapes.update({p + "_router_weight": (e, m),
                           p + "_experts_gate_weight": (held, f, m),
                           p + "_experts_up_weight": (held, f, m),
                           p + "_experts_down_weight": (held, m, f)})
            if cfg.router == "sigmoid_group":
                shapes[p + "_router_bias"] = (e,)
            if cfg.shared_experts:
                fs = cfg.shared_experts * f
                shapes.update({p + "_shared_gate_weight": (fs, m),
                               p + "_shared_up_weight": (fs, m),
                               p + "_shared_down_weight": (m, fs)})
        else:
            fd = cfg.dense_ffn_dim
            shapes.update({
                p + "_ffn1_weight": ((2 if cfg.ffn_gated else 1) * fd, m),
                p + "_ffn2_weight": (m, fd)})
            if cfg.bias:
                shapes.update({
                    p + "_ffn1_bias": ((2 if cfg.ffn_gated else 1) * fd,),
                    p + "_ffn2_bias": (m,)})
    return shapes


def _mixer_shapes(cfg, kind, p):
    """The weights of one layer's mixer, for the kinds other than "attn".
    Matrices are ``(out, in)`` as everywhere; ``ssm_a_log`` is ``(N, Dn)``,
    the state's own layout (a checkpoint's ``A_log`` transposed)."""
    m, hd, dn, n = cfg.model_dim, cfg.head_dim, cfg.d_inner, cfg.ssm_state
    hq, hkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    if kind == "mamba":
        return {p + "_ssm_in_weight": (2 * dn, m),
                p + "_ssm_conv_weight": (cfg.ssm_conv, dn),
                p + "_ssm_conv_bias": (dn,),
                p + "_ssm_x_weight": (cfg.ssm_dt_rank + 2 * n, dn),
                p + "_ssm_dt_weight": (dn, cfg.ssm_dt_rank),
                p + "_ssm_dt_bias": (dn,), p + "_ssm_a_log": (n, dn),
                p + "_ssm_d": (dn,), p + "_ssm_out_weight": (m, dn)}
    if kind == "gmu":
        return {p + "_gmu_in_weight": (dn, m), p + "_gmu_out_weight": (m, dn)}
    if kind == "mla":
        h, dr, dv = cfg.num_heads, cfg.rope_dim, cfg.v_dim
        return {p + "_mla_q_down_weight": (cfg.q_rank, m),
                p + "_mla_q_norm_gamma": (cfg.q_rank,),
                p + "_mla_q_up_weight": (h * (hd + dr), cfg.q_rank),
                p + "_mla_kv_down_weight": (cfg.kv_rank + dr, m),
                p + "_mla_kv_norm_gamma": (cfg.kv_rank,),
                # a head's rows: its key part without position, its value
                p + "_mla_kv_up_weight": (h * (hd + dv), cfg.kv_rank),
                p + "_attn_out_weight": (m, h * dv)}
    if kind == "kda":
        # keys and values are one width, which is both gate pairs' rank
        r = cfg.kda_head_dim
        hk = hv = cfg.kda_heads * r
        return {p + "_kda_in_weight": (cfg.kda_channels, m),    # [q; k; v]
                p + "_kda_conv_weight": (cfg.kda_conv, cfg.kda_channels),
                p + "_kda_f1_weight": (r, m), p + "_kda_f2_weight": (hk, r),
                p + "_kda_a_log": (cfg.kda_heads,),
                p + "_kda_dt_bias": (hk,),
                p + "_kda_b_weight": (cfg.kda_heads, m),
                p + "_kda_g1_weight": (r, m), p + "_kda_g2_weight": (hv, r),
                p + "_kda_g_bias": (hv,),
                p + "_kda_norm_gamma": (r,),
                p + "_kda_out_weight": (m, hv)}
    if cfg.gqa:
        hk, dv = cfg.kv_heads_of(kind), cfg.v_dim
        shapes = {p + "_attn_in_weight": (hq + hk * (hd + dv), m),
                  p + "_attn_out_weight": (m, cfg.num_heads * dv)}
        if kind == "swa" and cfg.swa_sink:
            shapes[p + "_attn_sink"] = (cfg.num_heads,)
        if cfg.attn_gate:
            shapes[p + "_attn_gate_weight"] = (cfg.num_heads * dv, m)
        return shapes
    shapes = {p + "_attn_out_weight": (m, hq),
              p + "_diff_norm_gamma": (2 * hd,)}
    shapes.update({p + "_diff_lambda_" + v: (hd,)
                   for v in ("q1", "k1", "q2", "k2")})
    if kind == "cross":
        shapes[p + "_attn_q_weight"] = (hq, m)
    else:
        shapes[p + "_attn_in_weight"] = (hq + 2 * hkv, m)
    if cfg.attn_bias:
        shapes[p + "_attn_out_bias"] = (m,)
        if kind == "cross":
            shapes[p + "_attn_q_bias"] = (hq,)
        else:
            shapes[p + "_attn_in_bias"] = (hq + 2 * hkv,)
    return shapes


def random_params(cfg, seed=0, dtype=np.float32):
    """Deterministic host-side random weights (gamma=1, beta/bias=0,
    weights ~N(0, 0.02)) — the same function call in any process yields
    byte-identical params, which is what lets the e2e test compare a
    served subprocess against an in-process sequential reference."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(param_shapes(cfg).items()):
        if name.endswith(("_gamma", "_ssm_d")):
            out[name] = np.ones(shape, dtype)
        elif name.endswith(("_ssm_dt_bias", "_kda_dt_bias")):
            # Mamba's init (and the delta rule's): dt log-uniform in [1e-3,
            # 1e-1], through the inverse of the softplus
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            out[name] = (dt + np.log(-np.expm1(-dt))).astype(dtype)
        elif name.endswith("_kda_a_log"):
            out[name] = np.log(rng.uniform(1.0, 16.0, shape)).astype(dtype)
        elif name.endswith("_ssm_a_log"):
            out[name] = np.broadcast_to(np.log(np.arange(
                1, shape[0] + 1, dtype=np.float64))[:, None],
                shape).astype(dtype)
        elif name.endswith("_router_bias"):
            # the selection's correction: zero would leave its path unseen
            out[name] = (rng.randn(*shape) * 0.01).astype(dtype)
        elif name.endswith(("_beta", "_bias")):
            out[name] = np.zeros(shape, dtype)
        elif name.endswith("_attn_sink"):
            # a learned sink is large: zero would be one part in window + 1
            # of the softmax, and its absence unseen
            out[name] = (2.0 + rng.randn(*shape)).astype(dtype)
        elif "_diff_lambda_" in name:
            out[name] = (rng.randn(*shape) * 0.1).astype(dtype)
        else:
            out[name] = (rng.randn(*shape) * _INIT_SCALE).astype(dtype)
    return out


def as_device_params(arg_params, cfg, dtype=None, device=None):
    """Stage a params dict (numpy / NDArray / jax values) onto the device,
    validating names+shapes against the config. Extra entries (e.g. a
    checkpoint's optimizer leftovers) are ignored."""
    import jax
    import jax.numpy as jnp

    want = param_shapes(cfg)
    out = {}
    missing = []
    for name, shape in want.items():
        if name not in arg_params:
            missing.append(name)
            continue
        a = arg_params[name]
        # asnumpy first: a numpy array of bfloat16 cannot show its .data
        a = a.data if hasattr(a, "asnumpy") and hasattr(a, "data") else a
        a = jnp.asarray(a, dtype=dtype)
        if tuple(a.shape) != tuple(shape):
            raise ValueError("param %s: shape %s != expected %s (config %r)"
                             % (name, tuple(a.shape), shape, cfg))
        out[name] = jax.device_put(a, device) if device is not None else a
    if missing:
        raise ValueError("params missing for serving config %r: %s"
                         % (cfg, sorted(missing)))
    return out


# ---------------------------------------------------------------------------
# functional blocks (numerics mirror models/transformer_lm.py op for op)
# ---------------------------------------------------------------------------


def draft_config(cfg, spec):
    """Resolve a draft-model selection (``MXNET_SERVING_DRAFT``) against a
    target config. ``"self"`` is the self-drafting harness — the draft IS
    the target shape (the engine then shares the target's weights, so
    greedy proposals match the verify pass and acceptance sits near 1.0);
    any other name must be a ``models/transformer_lm.py``
    ``SERVING_DRAFT_PRESETS`` entry (a tiny zoo shape, the GPT-2 block).
    vocab_size and max_len always follow the target: the draft proposes
    tokens from the same vocabulary at the same absolute positions."""
    from ..models.transformer_lm import SERVING_DRAFT_PRESETS

    if spec == "self":
        return ModelConfig(**{k: getattr(cfg, k)
                              for k in ModelConfig.__slots__})
    if spec not in SERVING_DRAFT_PRESETS:
        raise ValueError(
            "unknown draft model %r: expected 'self' or one of %s "
            "(models/transformer_lm.py SERVING_DRAFT_PRESETS)"
            % (spec, sorted(SERVING_DRAFT_PRESETS)))
    p = SERVING_DRAFT_PRESETS[spec]
    return ModelConfig(cfg.vocab_size, p["num_layers"], p["model_dim"],
                       p["num_heads"], p["ffn_dim"], cfg.max_len)


_NORM_EPS = 1e-5


def _layer_norm(x, gamma, beta, eps=_NORM_EPS):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def _rms_norm(x, gamma, eps=_NORM_EPS):
    """t / sqrt(mean(t^2) + eps) * gamma, statistics in float32."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
    return y.astype(x.dtype)


def _norm(x, params, name, cfg):
    if cfg.norm == "rms":
        return _rms_norm(x, params[name + "_gamma"], cfg.norm_eps)
    return _layer_norm(x, params[name + "_gamma"], params[name + "_beta"],
                       cfg.norm_eps)


def _rope(x, positions, cfg):
    """Rotate-half rotary position on ``x`` (A, B, H*hd) at absolute
    ``positions`` (A, B): the first and second half of a head are paired,
    angle = position * theta^(-2i/hd). Computed in float32."""
    import jax.numpy as jnp

    a, b, _ = x.shape
    hd = cfg.head_dim
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(hd // 2, dtype=jnp.float32) * 2.0 / hd)
    return _rotate(x.reshape(a, b, cfg.num_heads, hd), positions,
                   inv_freq).reshape(x.shape)


def _rotate(x4, positions, inv_freq):
    """Rotate-half on the last axis of ``x4`` (A, B, H, d) at ``positions``
    (A, B) with ``inv_freq`` (d // 2,), in float32."""
    import jax.numpy as jnp

    half = x4.shape[-1] // 2
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)                 # (A, B, 1, half)
    x32 = x4.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x4.dtype)


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def mla_rope(cfg):
    """``(inv_freq (rope_dim // 2,) float32, cos/sin scale)`` of the "mla"
    kind's rotary lanes: plain RoPE, or YaRN's (arXiv:2309.00071) — the
    frequencies below ``beta_slow`` rotations over the original length
    interpolated by ``factor``, those above ``beta_fast`` kept, a linear
    ramp between — with cos and sin scaled by ``mscale / mscale_all_dim``."""
    d = cfg.rope_dim
    extra = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if cfg.rope_yarn is None:
        return extra.astype(np.float32), 1.0
    factor, orig, fast, slow, mscale, mscale_all = cfg.rope_yarn

    def correction(rotations):
        return d * np.log(orig / (rotations * 2 * np.pi)) / (
            2 * np.log(cfg.rope_theta))

    low = max(np.floor(correction(fast)), 0)
    high = min(np.ceil(correction(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = extra / factor * ramp + extra * (1 - ramp)
    return inv_freq.astype(np.float32), (_yarn_mscale(factor, mscale)
                                         / _yarn_mscale(factor, mscale_all))


def mla_sm_scale(cfg):
    """The "mla" kind's softmax scale: ``(head_dim + rope_dim) ** -0.5``,
    times YaRN's ``mscale(factor, mscale_all_dim) ** 2``."""
    scale = float(cfg.head_dim + cfg.rope_dim) ** -0.5
    if cfg.rope_yarn is not None and cfg.rope_yarn[5]:
        scale *= _yarn_mscale(cfg.rope_yarn[0], cfg.rope_yarn[5]) ** 2
    return scale


def _position(q, k, positions, cfg):
    """The position scheme's part of the projections: rotary models turn
    q and k (K is cached rotated); a learned table was added to the
    embedding and leaves them alone."""
    if cfg.pos != "rope":
        return q, k
    return _rope(q, positions, cfg), _rope(k, positions, cfg)


def _embed(params, tokens, positions, cfg):
    """Token embedding (+ the learned position rows). ``positions`` has
    the shape of ``tokens``, or is None for 0..S-1 of a (1, S) prefill."""
    import jax.numpy as jnp

    x = jnp.take(params["embed_weight"], tokens, axis=0)
    if cfg.pos != "learned":        # rotary, or no position at all
        return x
    if positions is None:
        return x + params["pos_embed_weight"][:, :tokens.shape[1]]
    pos_tab = params["pos_embed_weight"].reshape(cfg.max_len, cfg.model_dim)
    return x + jnp.take(pos_tab, positions, axis=0)


def _gated(x2d, gate, up, down, prec):
    """``W_down (silu(W_gate h) * (W_up h))``: the shared expert."""
    import jax
    import jax.numpy as jnp

    f = jax.nn.silu(jnp.dot(x2d, gate.T, precision=prec)) \
        * jnp.dot(x2d, up.T, precision=prec)
    return jnp.dot(f, down.T, precision=prec)


def _ffn(x2d, params, prefix, cfg, prec):
    import jax.numpy as jnp

    f = jnp.dot(x2d, params[prefix + "_ffn1_weight"].T, precision=prec)
    if cfg.bias:
        f = f + params[prefix + "_ffn1_bias"]
    if cfg.ffn_gated:                   # [gate, up] = W1 h
        import jax

        gate, up = jnp.split(f, 2, axis=-1)
        f = jax.nn.silu(gate) * up
    else:
        f = jnp.maximum(f, 0)
    f = jnp.dot(f, params[prefix + "_ffn2_weight"].T,
                precision=prec)
    return f + params[prefix + "_ffn2_bias"] if cfg.bias else f


def _head(x2d, params, cfg, prec):
    """Final-norm'd rows (R, M) -> logits (R, V), float32 whatever the
    weights' type."""
    import jax.numpy as jnp

    head = params["embed_weight" if cfg.tie_embed else "lm_head_weight"]
    logits = jnp.dot(x2d, head.T, precision=prec,
                     preferred_element_type=jnp.float32)
    return logits + params["lm_head_bias"] if cfg.bias else logits


def _mix_attn(h, params, p, i, cfg, prec, positions, attend, state):
    """The "attn" kind: fused q/k/v projections (QK-norm, position) ->
    ``attend`` -> output projection."""
    import jax.numpy as jnp

    qkv = jnp.einsum("bsm,nm->bsn", h, params[p + "_attn_in_weight"],
                     precision=prec)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    if cfg.qk_norm:
        q = _rms_norm(q, params[p + "_q_norm_gamma"])
        k = _rms_norm(k, params[p + "_k_norm_gamma"])
    q, k = _position(q, k, positions, cfg)
    attn, state = attend(i, q, k, v, state)
    attn = jnp.einsum("bsm,nm->bsn", attn, params[p + "_attn_out_weight"],
                      precision=prec)
    return attn, state


def _mix_mla(h, params, p, i, cfg, prec, positions, attend, state):
    """The "mla" kind: the query through its normalised bottleneck, the
    latent and the one rotary key from ``W_dkv`` (the latent normalised,
    the key rotated: what the cache holds) -> ``attend`` -> ``W_o``.
    ``attend(i, (q_n, q_r), c, k_r, state)`` gets the heads' two query
    parts ``(A, B, H, head_dim)`` / ``(A, B, H, rope_dim)``, the latent
    ``(A, B, kv_rank)`` and the key ``(A, B, rope_dim)``, and returns the
    heads' values side by side ``(A, B, H v_dim)``."""
    import jax.numpy as jnp

    a, b, _ = h.shape
    hh, dn, dr = cfg.num_heads, cfg.head_dim, cfg.rope_dim
    inv_freq, rscale = mla_rope(cfg)

    def proj(t, name):
        return jnp.einsum("bsm,nm->bsn", t, params[p + name], precision=prec)

    cq = _rms_norm(proj(h, "_mla_q_down_weight"),
                   params[p + "_mla_q_norm_gamma"], cfg.norm_eps)
    q = proj(cq, "_mla_q_up_weight").reshape(a, b, hh, dn + dr)
    c, kr = jnp.split(proj(h, "_mla_kv_down_weight"), [cfg.kv_rank], axis=-1)
    c = _rms_norm(c, params[p + "_mla_kv_norm_gamma"], cfg.norm_eps)
    qr = _rotate(q[..., dn:], positions, inv_freq)
    kr = _rotate(kr[:, :, None], positions, inv_freq)[:, :, 0]
    if rscale != 1.0:
        qr, kr = qr * rscale, kr * rscale
    att, state = attend(i, (q[..., :dn], qr), c, kr, state)
    return proj(att, "_attn_out_weight"), state


def _rope_part(t, positions, theta, dr):
    """Rotate-half rotary position on the first ``dr`` lanes of every head
    of ``t`` (A, B, H, hd) at ``positions`` (A, B); the rest pass through."""
    import jax.numpy as jnp

    dr = dr or t.shape[-1]
    inv_freq = theta ** (-jnp.arange(dr // 2, dtype=jnp.float32) * 2.0 / dr)
    turned = _rotate(t[..., :dr], positions, inv_freq)
    return turned if dr == t.shape[-1] \
        else jnp.concatenate([turned, t[..., dr:]], -1)


def _mix_gqa(h, params, p, i, kind, cfg, prec, positions, attend, state):
    """The "swa" / "full" kinds of ``attn_form`` "gqa": plain softmax
    attention, ``num_heads`` query heads over the kind's K/V heads, values
    ``v_dim`` wide. ``attend(i, q, k, v, state)`` gets ``(A, B, H, hd)``,
    ``(A, B, Hkv, hd)`` and ``(A, B, Hkv, dv)`` (q and k turned on their
    rotary lanes, K cached turned) and returns the heads' results
    ``(A, B, H, dv)``; the sink is ``attend``'s to apply."""
    import jax.numpy as jnp

    a, b, _ = h.shape
    hh, hd, dv, hk = (cfg.num_heads, cfg.head_dim, cfg.v_dim,
                      cfg.kv_heads_of(kind))
    qkv = jnp.einsum("bsm,nm->bsn", h, params[p + "_attn_in_weight"],
                     precision=prec)
    q, k, v = jnp.split(qkv, [hh * hd, (hh + hk) * hd], axis=-1)
    q, k = q.reshape(a, b, hh, hd), k.reshape(a, b, hk, hd)
    if cfg.pos == "rope":
        theta = cfg.rope_theta_of(kind)
        q = _rope_part(q, positions, theta, cfg.rope_dim)
        k = _rope_part(k, positions, theta, cfg.rope_dim)
    att, state = attend(i, q, k, v.reshape(a, b, hk, dv), state)
    if cfg.value_scale != 1.0:
        att = att * jnp.asarray(cfg.value_scale, att.dtype)
    att = att.reshape(a, b, hh * dv)
    if cfg.attn_gate:
        import jax

        att = att * jax.nn.sigmoid(jnp.einsum(
            "bsm,nm->bsn", h, params[p + "_attn_gate_weight"],
            precision=prec))
    return jnp.einsum("bsm,nm->bsn", att, params[p + "_attn_out_weight"],
                      precision=prec), state


def _sink(params, i, cfg):
    """Layer i's sink a query head, float32 ``(H,)``; None without one."""
    import jax.numpy as jnp

    if not (cfg.swa_sink and cfg.kinds()[i] == "swa"):
        return None
    return params["layer%d_attn_sink" % i].astype(jnp.float32)


def _pad_lanes(t, lanes):
    import jax.numpy as jnp

    return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, lanes - t.shape[-1])])


def diff_lambda_init(i):
    """Differential attention's ``lambda_init`` of layer ``i`` (0-indexed)."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * i))


def _diff_lambda(params, p, i):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, float32."""
    import jax.numpy as jnp

    def term(a, b):
        return jnp.exp(jnp.sum(
            params[p + "_diff_lambda_" + a].astype(jnp.float32)
            * params[p + "_diff_lambda_" + b].astype(jnp.float32)))

    return term("q1", "k1") - term("q2", "k2") + diff_lambda_init(i)


def _mix_diff(h, params, p, i, kind, cfg, prec, attend, state):
    """The "swa" / "full" / "cross" kinds: differential attention.
    ``attend`` returns, for every query pair, its two heads' softmax
    attention over the pair's 2 hd-wide values: ``(A, B, P, 2, 2 hd)``.
    The pair's output is ``a_1 V - lambda a_2 V``, RMS-normed over its
    2 hd lanes and scaled by ``1 - lambda_init``; statistics in float32."""
    import jax.numpy as jnp

    f32 = jnp.float32
    hq, hkv = (cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim)
    if kind == "cross":
        q = jnp.einsum("bsm,nm->bsn", h, params[p + "_attn_q_weight"],
                       precision=prec)
        if cfg.attn_bias:
            q = q + params[p + "_attn_q_bias"]
        k = v = None
    else:
        qkv = jnp.einsum("bsm,nm->bsn", h, params[p + "_attn_in_weight"],
                         precision=prec)
        if cfg.attn_bias:
            qkv = qkv + params[p + "_attn_in_bias"]
        q, k, v = jnp.split(qkv, [hq, hq + hkv], axis=-1)
    att, state = attend(i, q, k, v, state)          # (A, B, P, 2, 2 hd)

    lam_init = diff_lambda_init(i)
    att = att.astype(f32)
    o = att[..., 0, :] - _diff_lambda(params, p, i) * att[..., 1, :]
    o = _rms_norm(o, params[p + "_diff_norm_gamma"]) * (1.0 - lam_init)
    o = o.reshape(h.shape[:2] + (hq,)).astype(h.dtype)
    out = jnp.einsum("bsm,nm->bsn", o, params[p + "_attn_out_weight"],
                     precision=prec)
    if cfg.attn_bias:
        out = out + params[p + "_attn_out_bias"]
    return out, state


def _diff_q_rows(q, cfg):
    """q ``(A, B, Hq hd)`` as rows that line up with the K/V pair rows
    ``[k1 | k2]``: query head ``2 p`` becomes ``[q | 0]`` and head
    ``2 p + 1`` ``[0 | q]``, so that a plain dot with the row is the head's
    own score. Returns ``(A, B, G, R, 2 hd)`` with R = the query heads that
    read K/V row g (pair-major: the two heads of the row's first query
    pair, then of its second, ...)."""
    import jax.numpy as jnp

    a, b, _ = q.shape
    hd, g = cfg.head_dim, cfg.num_kv_heads // 2
    pairs = q.reshape(a, b, cfg.num_heads // 2, 2, hd)
    zero = jnp.zeros_like(pairs[..., 0, :])
    rows = jnp.stack([jnp.concatenate([pairs[..., 0, :], zero], -1),
                      jnp.concatenate([zero, pairs[..., 1, :]], -1)], -2)
    return rows.reshape(a, b, g, cfg.num_heads // g, 2 * hd)


def _ssm_inputs(xc, params, p, cfg, prec):
    """From the conv'd, silu'd input rows (R, Dn): ``dt`` before the
    softplus (R, Dn) float32, ``B`` and ``C`` (R, N) float32."""
    import jax.numpy as jnp

    f32 = jnp.float32
    r, n = cfg.ssm_dt_rank, cfg.ssm_state
    dbc = jnp.dot(xc, params[p + "_ssm_x_weight"].T, precision=prec,
                  preferred_element_type=f32)
    dt_low, b, c = jnp.split(dbc, [r, r + n], axis=-1)
    dt = jnp.dot(dt_low.astype(xc.dtype), params[p + "_ssm_dt_weight"].T,
                 precision=prec, preferred_element_type=f32)
    return dt + params[p + "_ssm_dt_bias"].astype(f32), b, c


def _conv_taps(window, params, p):
    """silu(depthwise conv + bias) of ``window`` (.., K, Dn): the K taps
    oldest first, the last the row's own. float32 in, the caller rounds."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = params[p + "_ssm_conv_weight"].astype(f32)              # (K, Dn)
    return jax.nn.silu(jnp.sum(window.astype(f32) * w, axis=-2)
                       + params[p + "_ssm_conv_bias"].astype(f32))


def _mix_mamba(h, params, p, i, cfg, prec, recur, state):
    """The "mamba" kind: ``[x, z] = W_in h`` -> ``recur`` (the conv, the
    scan and the gate, over the step's own state) -> ``W_out``. The memory
    layer leaves its scan output ``y`` (before the gate, ``D x`` included)
    in ``state["m"]`` for the "gmu" layers of the same step."""
    import jax.numpy as jnp

    xz = jnp.einsum("bsm,nm->bsn", h, params[p + "_ssm_in_weight"],
                    precision=prec)
    xin, z = jnp.split(xz, 2, axis=-1)
    y, out, state = recur(i, xin, z, state)
    if i == cfg.memory_layer:
        state = dict(state, m=y)
    return jnp.einsum("bsm,nm->bsn", out, params[p + "_ssm_out_weight"],
                      precision=prec), state


def _kda_conv(window, w):
    """silu(depthwise conv) of ``window``, the ``K`` taps' rows oldest first
    (each ``(.., C)``), under taps ``w`` (K, C): K shifted multiply-adds in
    float32, no bias. The caller rounds."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = w.astype(f32)
    return jax.nn.silu(sum(t.astype(f32) * w[j] for j, t in enumerate(window)))


def _kda_heads(xc, cfg):
    """The conv'd rows ``(R, C)`` as heads: q ``(R, H, dk)`` L2-normalised
    and scaled by ``dk ** -0.5``, k ``(R, H, dk)`` L2-normalised, v ``(R, H,
    dk)``; the norms in float32, the results in the rows' type."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hh, dk = cfg.kda_heads, cfg.kda_head_dim     # values are dk wide too
    q, k, v = jnp.split(xc, [hh * dk, 2 * hh * dk], axis=-1)

    def unit(t, scale):
        t = t.reshape(-1, hh, dk).astype(f32)
        return (t * (jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
                     * scale)).astype(xc.dtype)

    return unit(q, float(dk) ** -0.5), unit(k, 1.0), v.reshape(-1, hh, dk)


def _mix_kda(h, params, p, i, cfg, prec, recur, state):
    """The "kda" kind: ``[q, k, v] = W_in h`` -> ``recur`` (the conv over the
    stream's tail, the heads' norms and the delta rule over the stream's
    state) -> a head's RMSNorm, the sigmoid gate, ``W_out``. The decay and
    the step size are the token's own (no state) and are made here, in
    float32: ``g = -exp(A_log) softplus(W_f2 W_f1 h + dt_bias)`` a key
    channel, ``beta = sigmoid(W_b h)`` a head, doubled where
    ``kda_neg_eigval`` (``I - beta k k^T`` then reaches eigenvalue -1)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    a, b, _ = h.shape
    hh, dk = cfg.kda_heads, cfg.kda_head_dim     # values are dk wide too

    def proj(t, name, **kw):
        return jnp.einsum("bsm,nm->bsn", t, params[p + name], precision=prec,
                          **kw)

    qkv = proj(h, "_kda_in_weight")
    f = proj(proj(h, "_kda_f1_weight"), "_kda_f2_weight",
             preferred_element_type=f32)
    g = -jnp.exp(params[p + "_kda_a_log"].astype(f32))[:, None] \
        * jax.nn.softplus(f + params[p + "_kda_dt_bias"].astype(f32)
                          ).reshape(a, b, hh, dk)
    beta = jax.nn.sigmoid(proj(h, "_kda_b_weight",
                               preferred_element_type=f32))
    if cfg.kda_neg_eigval:
        beta = beta * 2.0
    _y, o, state = recur(i, qkv, (g, beta), state)      # (A, B, H, dk)
    o = _rms_norm(o, params[p + "_kda_norm_gamma"], cfg.norm_eps)
    gate = jax.nn.sigmoid(
        proj(proj(h, "_kda_g1_weight"), "_kda_g2_weight")
        + params[p + "_kda_g_bias"])
    return proj(o.reshape(a, b, hh * dk) * gate, "_kda_out_weight"), state


def _mix_gmu(h, params, p, prec, state):
    """The "gmu" kind: ``W_out (silu(W_in h) * m)``, ``m`` the memory
    layer's scan output of the same token."""
    import jax
    import jax.numpy as jnp

    g = jnp.einsum("bsm,nm->bsn", h, params[p + "_gmu_in_weight"],
                   precision=prec)
    return jnp.einsum("bsm,nm->bsn", jax.nn.silu(g) * state["m"],
                      params[p + "_gmu_out_weight"], precision=prec)


def _layer(x, params, i, cfg, prec, positions, valid, attend, state,
           recur=None):
    """THE layer body, shared by :func:`prefill`, :func:`decode` and
    :func:`extend`: norm -> the layer's mixer -> norm -> FFN or routed
    experts. The mixer is the layer's KIND's (``ModelConfig``): q/k/v
    projections (QK-norm, position) -> ``attend`` -> output projection
    for "attn", and :func:`_mix_diff` (or :func:`_mix_gqa`),
    :func:`_mix_mamba`, :func:`_mix_gmu` for the others.

    x:         (A, B, M) — (1, S, M) in prefill, (B, 1, M) in decode,
               (B, T, M) in the verify pass
    positions: (A, B) int32 absolute positions (rotary models read them)
    valid:     (A, B) bool — live lanes, for the experts' load count
    attend:    ``(i, q, k, v, state) -> (attn, state)``: what differs
               between the three steps — where this layer's K and V go and
               which attention reads them. ``state`` is the caller's (the
               pages, or the K/V collected so far).
    recur:     ``(i, x, z, state) -> (y, out, state)``: the same for a
               "mamba" layer's conv tail and state, and for a "kda" layer's
               (``x`` its three projections, ``z`` its decay and step size,
               ``y`` None).

    Returns ``(x, state, tokens_per_expert (E,) or None)``."""
    p = "layer%d" % i
    h = _norm(x, params, p + "_ln1", cfg)
    kind = cfg.kinds()[i]
    if kind == "attn":
        mix, state = _mix_attn(h, params, p, i, cfg, prec, positions, attend,
                               state)
    elif kind == "mamba":
        mix, state = _mix_mamba(h, params, p, i, cfg, prec, recur, state)
    elif kind == "gmu":
        mix = _mix_gmu(h, params, p, prec, state)
    elif kind == "mla":
        mix, state = _mix_mla(h, params, p, i, cfg, prec, positions, attend,
                              state)
    elif kind == "kda":
        import jax

        with jax.named_scope("kda"):
            mix, state = _mix_kda(h, params, p, i, cfg, prec, recur, state)
    elif cfg.gqa:
        import jax

        # the two kinds' ops under two names on a trace
        with jax.named_scope("gqa_" + kind):
            mix, state = _mix_gqa(h, params, p, i, kind, cfg, prec,
                                  positions, attend, state)
    else:
        mix, state = _mix_diff(h, params, p, i, kind, cfg, prec, attend,
                               state)
    if cfg.post_norm:
        mix = _norm(mix, params, p + "_ln1_post", cfg)
    x = x + mix
    h = _norm(x, params, p + "_ln2", cfg)
    a, b, m = x.shape
    load = None
    h2 = h.reshape(a * b, m)
    if cfg.num_experts and i >= cfg.first_dense:
        how = {}
        if cfg.router != "softmax":
            how = dict(kind=cfg.router, bias=params[p + "_router_bias"],
                       n_group=cfg.n_group, topk_group=cfg.topk_group,
                       scale=cfg.route_scale)
        if cfg.experts_held is not None:
            how["held"] = cfg.experts_held
        f, load = moe_ffn(
            h2, params[p + "_router_weight"],
            params[p + "_experts_gate_weight"],
            params[p + "_experts_up_weight"],
            params[p + "_experts_down_weight"], cfg.experts_per_tok,
            valid=valid.reshape(a * b), **how)
        if cfg.shared_experts:
            f = f + _gated(h2, params[p + "_shared_gate_weight"],
                           params[p + "_shared_up_weight"],
                           params[p + "_shared_down_weight"], prec)
    else:
        f = _ffn(h2, params, p, cfg, prec)
    f = f.reshape(a, b, m)
    if cfg.post_norm:
        f = _norm(f, params, p + "_ln2_post", cfg)
    return x + f, state, load


def _layers(x, params, cfg, prec, positions, valid, attend, state,
            recur=None):
    """Every layer in turn and the final norm: ``(x, state, loads)`` with
    ``loads`` the expert layers' ``tokens_per_expert`` stacked
    ``(expert_layers, E)``, or () without experts.

    A looped model (``loop_steps`` > 1) goes through that ``loop_steps``
    times in ONE ``lax.fori_loop``: the program holds one pass's layer
    bodies however many passes it runs, a pass's normed result is the
    next's input, and ``attend`` is handed the pass ``r`` (traced) to find
    that pass's cache by; ``state`` rides the loop, so it keeps its
    structure (the pages, not K/V collected along the way)."""
    import functools

    import jax
    import jax.numpy as jnp

    def stack(x, state, attend):
        loads = []
        for i in range(cfg.num_layers):
            x, state, load = _layer(x, params, i, cfg, prec, positions,
                                    valid, attend, state, recur)
            if load is not None:
                loads.append(load)
        return _norm(x, params, "final_ln", cfg), state, loads

    if cfg.loop_steps == 1:
        x, state, loads = stack(x, state, attend)
        return x, state, ((jnp.stack(loads),) if cfg.num_experts else ())

    def one_pass(r, carry):
        with jax.named_scope("looped_pass"):
            x, state, _ = stack(*carry, functools.partial(attend, r=r))
        return x, state

    x, state = jax.lax.fori_loop(0, cfg.loop_steps, one_pass, (x, state))
    return x, state, ()


#: the most prompts one prefill program takes (:func:`pack_width`): a pack
#: of sixteen short prompts already fills a long rung, and the head's rows
#: and the fetch grow with it
PACK_MAX = 16

#: a pack's prompts start on whole sublane tiles of rows, not on blocks: a
#: row costs the device the same in any program (PERF.md section 6, PR 51),
#: so what a pack saves is the rows it does not compute
PACK_ALIGN = 8


def _one_prompt(cfg):
    """Whether a prefill program of this model takes ONE prompt, its bare
    length the argument — the program it always traced, to the character.
    A model with state slots ("mamba" and "kda" layers): its scan and its
    chunkwise delta rule start from an empty state and conv tail at row 0
    only. And a model without experts: a row costs the device the same in
    any program, so a pack saves the rows it does not compute and nothing
    else — within the noise where it was read (plain +0.9%, looped +0.6%) —
    while a prompt ALONE pays the pack's gather, floor and wider table (+6.8%
    on ``gpt2m-longprompt-open``'s median latency: PERF.md section 6, PR
    51). With experts a pack also reads every held expert's weights once
    and not once a prompt (``gmm`` -43% in dots.vlm1's prefill)."""
    return bool(cfg.layers_of("mamba", "kda")) or not cfg.num_experts


def pack_width(cfg, rows):
    """P, the prompts the prefill program of ``rows`` rows takes end to end:
    a function of the program's shape alone, so a rung stays ONE executable;
    1 for a model that is not packed (:func:`_one_prompt`)."""
    if _one_prompt(cfg):
        return 1
    return min(rows // PACK_ALIGN, PACK_MAX)


def pack_blocks(cfg, rows, block_size):
    """The width of that program's block table: every prompt's rows are
    rounded up to whole blocks in the CACHE (not along the program's rows),
    so P prompts in ``rows`` rows take up to ``rows // block_size + P - 1``."""
    return rows // block_size + pack_width(cfg, rows) - 1


def pack_of(cfg, rows, spans):
    """What the prefill program of ``rows`` rows is told of the prompts at
    ``spans``, ``[(first row, length), ...]`` in row order (its ``length``
    argument, host side): a model that takes one prompt a program gets the
    prompt's length, () int32 — the argument it always had; every other the
    pack, (2, P) int32 ``[starts, lengths]``, its unused entries empty at
    ``rows``. A pack of one is the same executable as a pack of P."""
    if _one_prompt(cfg):
        (_start, length), = spans
        return np.int32(length)
    pack = np.zeros((2, pack_width(cfg, rows)), np.int32)
    pack[0] = rows
    pack[:, :len(spans)] = np.asarray(spans, np.int32).reshape(-1, 2).T
    return pack


def _pack_rows(length, S, bs):
    """What a prefill program of ``S`` rows knows of each row, from what it
    is told of its prompts: ``(positions (1, S), its table-lookup form or
    None, first_key (S,) or None, cached, valid, last)`` — the last two made
    when CALLED, where the program uses them: ``valid()`` (1, S) bool, the
    rows that hold a prompt's token, and ``last()``, the prompts' last rows.
    ``cached(t, axis=0)`` puts a layer's K or V rows (S of them along
    ``axis``) in the order of the block table's slots.

    ``length`` () int32 is ONE prompt from row 0 — rows 0..S-1 are its
    positions, no floor, its rows the table's slots as they stand,
    ``last()`` () its last token's row: the program there always was,
    operation for operation. ``length`` (2, P) int32 is a PACK, ``[starts,
    lengths]``: prompt p holds rows ``starts[p] .. starts[p] + lengths[p] -
    1``, the starts ascending; an unused entry has length 0 and starts at
    ``S``. A row belongs to the last prompt that starts at or before it
    (the gap up to the next start computes garbage, as a padded tail does):
    its position counts from that start, which is also the first key it may
    see; ``last()`` is (P,). In the CACHE a prompt starts on a block
    boundary, behind the blocks of the prompts before it: slot c of the
    table's ``S // bs + P - 1`` blocks of ``bs`` slots takes the row of its
    prompt's token c counts to (a slot past the prompt's end: any row, as a
    padded tail's)."""
    import jax.numpy as jnp

    rows = jnp.arange(S, dtype=jnp.int32)
    if jnp.ndim(length) == 0:
        positions = rows[None]
        return (positions, None, None, lambda t, axis=0: t,
                lambda: positions < length, lambda: length - 1)
    starts, lengths = length[0], length[1]
    prompt = jnp.sum(rows[:, None] >= starts[None], axis=1) - 1
    first_key = jnp.take(starts, prompt)
    positions = (rows - first_key)[None]
    # a prompt's first block in the table: the blocks of those before it
    blocks = -(-lengths // bs)
    first_block = jnp.cumsum(blocks) - blocks
    slots = jnp.arange((S // bs + lengths.shape[0] - 1) * bs, dtype=jnp.int32)
    owner = jnp.sum(slots[:, None] // bs >= first_block[None], axis=1) - 1
    slot_rows = jnp.minimum(
        jnp.take(starts, owner) + slots - jnp.take(first_block, owner) * bs,
        S - 1)
    return (positions, positions, first_key,
            lambda t, axis=0: jnp.take(t, slot_rows, axis=axis),
            lambda: positions < jnp.take(lengths, prompt)[None],
            lambda: jnp.clip(starts + lengths - 1, 0, S - 1))


def _last_rows(x, last):
    """The final-norm'd rows ``last()`` of ``x`` (1, S, M) for the head:
    ``(P, M)``, a lone prompt's ``(1, M)``."""
    import jax.numpy as jnp

    h_last = jnp.take(x[0], last(), axis=0)
    return h_last if h_last.ndim == 2 else h_last[None]


def prefill(params, tokens, length, block_table, k_pages, v_pages, cfg,
            aux=None):
    """Full-sequence prefill at a padded bucket length, for ONE request or
    for a PACK of them laid end to end along the rows (each from a row of
    :data:`PACK_ALIGN`'s multiple) and attending to itself alone.

    tokens:      (1, S) int32, S a bucket multiple of the pool block size
                 (a prompt left-aligned at its start, gaps and the tail
                 padded with 0s)
    length:      () int32 — the one prompt's true length (1 <= length <=
                 S), or (2, P) int32 — a pack's ``[starts, lengths]``
                 (:func:`_pack_rows`; P = :func:`pack_width`)
    block_table: (S // block_size,) int32 — the request's allocated blocks
                 in position order, entries past the prompt = 0 (trash); a
                 pack's (:func:`pack_blocks` entries): the prompts' tables
                 one after another, each of whole blocks, then 0s
    k/v_pages:   the pool pages, (L, N, bs, G, W) — donated by the engine

    Returns ``(next_token (P,) int32, logits (P, V), k_pages, v_pages)``
    (P = 1 for the one prompt) — and, for a config with experts, a fifth
    result: the per-layer ``tokens_per_expert`` (L, E) int32 of the live
    tokens. Every layer's K/V for rows < S is scattered into the pool
    through the table, and the greedy next token sampled at each prompt's
    last row. Attention is the training block's
    ``flash_attention(causal=True)``, with a floor a row for a pack
    (``first_key``: a prompt's rows see no earlier prompt's) — padded rows
    compute garbage but cannot reach a prompt's own rows (causal mask) and
    their cache writes land in trash-table blocks.

    A looped model (``loop_steps`` = R) takes pages of R parts,
    ``(L, R x N, bs, G, W)``, and writes layer i's K/V of pass r into blocks
    ``block_table + r x N`` as the layer is computed (``KVBlockPool``).

    A model with ``layer_kinds`` takes ``aux`` — ``wtable`` (S // bs,) the
    stream's window-pool blocks (0 for those behind the window: a long
    prompt keeps its tail only), ``slot`` () its state slot, and the
    donated ``wk`` / ``wv`` (window pool pages), ``conv`` (Ls, NS, (K-1) Dn)
    and ``ssm`` (Ls, NS, N, Dn) — and returns the four arrays as a fifth
    result, a dict (:func:`_prefill_hybrid`).
    """
    import jax.numpy as jnp

    if cfg.hybrid:
        return _prefill_hybrid(params, tokens, length, block_table, k_pages,
                               v_pages, cfg, aux)
    _, S = tokens.shape
    hh, hd = cfg.num_heads, cfg.head_dim
    bs, rows, lanes = k_pages.shape[2:]
    prec = fp32_precision(k_pages.dtype)
    positions, table_pos, first_key, cached, valid, last = _pack_rows(
        length, S, bs)

    def split_heads(t):
        return t.reshape(1, S, hh, hd).transpose(0, 2, 1, 3)   # (1, H, S, hd)

    def flash(q, k, v):
        attn = flash_attention(split_heads(q), split_heads(k),
                               split_heads(v), True, first_key=first_key)
        return attn.transpose(0, 2, 1, 3).reshape(1, S, hh * hd)

    def attend(i, q, k, v, kv):
        # kept in the table's order layer by layer: no second copy of
        # every layer's K/V stands beside the stack
        return flash(q, k, v), (kv[0] + (cached(k, 1),),
                                kv[1] + (cached(v, 1),))

    def attend_pass(i, q, k, v, pages, r):
        """A looped model's: layer i's K/V of pass r go into that pass's
        part of the pool as the layer is computed (a pass's K/V, let alone
        every pass's, is never held beside the pool)."""
        table = block_table + r * (k_pages.shape[1] // cfg.loop_steps)
        return flash(q, k, v), tuple(
            _put_blocks(pg, i, table,
                        cached(t, 1).reshape(-1, bs, rows, lanes))
            for pg, t in zip(pages, (k, v)))

    x = _embed(params, tokens, table_pos, cfg)                 # (1, S, M)
    if cfg.loop_steps > 1:
        x, (k_pages, v_pages), loads = _layers(
            x, params, cfg, prec, positions, valid(), attend_pass,
            (k_pages, v_pages))
    else:
        x, (k_all, v_all), loads = _layers(
            x, params, cfg, prec, positions, valid(), attend, ((), ()))
        # scatter every layer's K/V through the block table (trash entries
        # absorb the padded tail)
        kw = jnp.stack(k_all).reshape(cfg.num_layers, -1, bs, rows, lanes)
        vw = jnp.stack(v_all).reshape(cfg.num_layers, -1, bs, rows, lanes)
        k_pages = k_pages.at[:, block_table].set(kw.astype(k_pages.dtype))
        v_pages = v_pages.at[:, block_table].set(vw.astype(v_pages.dtype))

    logits = _head(_last_rows(x, last), params, cfg, prec)     # (P, V)
    next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (next_token, logits, k_pages, v_pages) + loads


def _cache_layers(cfg):
    """Model layer -> its layer of the full pool, of the window pool, of
    the state slots: three dicts (the pools' layers are not the model's)."""
    return tuple({i: n for n, i in enumerate(cfg.layers_of(*kinds))}
                 for kinds in (("full", "mla"), ("swa",), ("mamba", "kda")))


def _block_rows(t, bs, rows, head_major):
    """K or V of S tokens ``(S, G W)`` as S // bs blocks of page rows
    ``rows`` in the pool's own order: ``(S // bs, bs, G, W)``, or
    ``(S // bs, G, bs, W)``."""
    t = t.reshape(t.shape[0] // bs, bs, *rows)
    return t.transpose(0, 2, 1, 3) if head_major else t


def _put_blocks(pages, li, table, rows):
    """One layer's K or V of a prompt, ``rows`` (S // bs, ...) in the
    pool's own block order, into the blocks ``table`` names."""
    import jax

    rows = rows.astype(pages.dtype)
    if rows.shape[0] == 1:
        # a scatter of ONE block is rewritten by the compiler into a
        # form that copies the pool in and out; the slice update it is
        return jax.lax.dynamic_update_slice(
            pages, rows[None], (li, table[0], 0, 0, 0))
    return pages.at[li, table].set(rows)


def _prefill_hybrid(params, tokens, length, block_table, k_pages, v_pages,
                    cfg, aux):
    """:func:`prefill` for a model with ``layer_kinds``. A layer's K/V is
    scattered as the layer is computed ("swa" layers into the window pool
    through ``wtable``, "full" layers into the full pool); "cross" layers
    read the last "full" layer's K and V of this same program; a "mamba"
    layer scans from a zero state (a prefill always starts a stream) and
    leaves its final state and conv tail in the stream's ``slot`` — which
    is why a model with such layers is handed one prompt, ``length`` ()
    (:func:`pack_width`)."""
    import jax
    import jax.numpy as jnp

    _, S = tokens.shape
    full, win = cfg.cache_specs()
    bs = k_pages.shape[full.block_axis]
    g, w = full.k_rows
    rq = cfg.num_heads // g             # query heads reading one K/V row
    prec = fp32_precision(k_pages.dtype)
    positions, table_pos, first_key, cached, valid, last = _pack_rows(
        length, S, bs)
    sm_scale = 1.0 / float(np.sqrt(cfg.head_dim))
    full_at, win_at, ssm_at = _cache_layers(cfg)
    wtable, slot = aux["wtable"], aux["slot"]
    taps = cfg.ssm_conv

    def put(pages, li, table, t, rows=full.k_rows):
        """One layer's K or V of the S tokens into its blocks."""
        return _put_blocks(pages, li, table,
                           _block_rows(cached(t), bs, rows, full.head_major))

    def attend_mla(i, q, c, kr, st):
        """Cache the latent and the rotated key; attend over the prompt's
        EXPANDED heads, as published: keys ``[k_n | k_r]`` (the one rotary
        key under every head), values ``v_dim`` wide."""
        hh, dv = cfg.num_heads, cfg.v_dim
        li = full_at[i]
        st = dict(st, k=put(st["k"], li, block_table, c[0]),
                  v=put(st["v"], li, block_table,
                        _pad_lanes(kr[0], full.v_rows[1]), full.v_rows))
        kv = jnp.einsum("sc,nc->sn", c[0],
                        params["layer%d_mla_kv_up_weight" % i],
                        precision=prec).reshape(S, hh, cfg.head_dim + dv)
        keys = jnp.concatenate(
            [kv[..., :cfg.head_dim],
             jnp.broadcast_to(kr[0][:, None], (S, hh, cfg.rope_dim))], -1)
        qs = jnp.concatenate(q, -1)[0]                  # (S, H, dn + dr)
        att = flash_attention(qs.transpose(1, 0, 2)[None],
                              keys.transpose(1, 0, 2)[None],
                              kv[..., cfg.head_dim:].transpose(1, 0, 2)[None],
                              True, mla_sm_scale(cfg),
                              first_key=first_key)      # (1, H, S, dv)
        return att[0].transpose(1, 0, 2).reshape(1, S, hh * dv), st

    def attend_gqa(i, q, k, v, st):
        """Cache K (its lanes padded to the page's) and V, each in its own
        rows, "swa" layers into the window pool; attend over the prompt
        with the K/V heads named by the kernel's index map, not repeated."""
        kind = cfg.kinds()[i]
        if kind == "full":
            kp, vp, table, li, spec = "k", "v", block_table, full_at[i], full
        else:
            kp, vp, table, li, spec = "wk", "wv", wtable, win_at[i], win
        krows, vrows = spec.k_rows, spec.v_rows
        st = dict(st, **{
            kp: put(st[kp], li, table, _pad_lanes(k[0], krows[1]), krows),
            vp: put(st[vp], li, table, _pad_lanes(v[0], vrows[1]), vrows)})
        att = flash_attention_gqa(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), sm_scale,
            cfg.window if kind == "swa" else None, _sink(params, i, cfg),
            first_key=first_key)
        if first_key is not None:
            # a pack's cache writes (a gather, then the scatter) are put
            # off by the compiler to the program's end, every layer's K
            # and V alive till then (0.37 GB in MiMo's top rung, compiled
            # for a v5e): they are done before the layer goes on
            att, written = jax.lax.optimization_barrier(
                (att, (st[kp], st[vp])))
            st = dict(st, **dict(zip((kp, vp), written)))
        return att.transpose(0, 2, 1, 3), st            # (1, S, H, dv)

    def attend(i, q, k, v, st):
        kind = cfg.kinds()[i]
        if kind == "mla":
            return attend_mla(i, q, k, v, st)
        if cfg.gqa:
            return attend_gqa(i, q, k, v, st)
        if kind == "cross":
            k, v = st["kv"]
        else:
            if kind == "full":
                st = dict(st, kv=(k, v))
                kp, vp, table, li = "k", "v", block_table, full_at[i]
            else:
                kp, vp, table, li = "wk", "wv", wtable, win_at[i]
            st = dict(st, **{kp: put(st[kp], li, table, k[0]),
                             vp: put(st[vp], li, table, v[0])})
        # (1, G R, S, W): query row r of K/V row g, against that row
        # repeated for its R readers
        qr = _diff_q_rows(q, cfg)[0].reshape(S, g * rq, w).transpose(1, 0, 2)
        kr, vr = (jnp.repeat(t[0].reshape(S, g, w).transpose(1, 0, 2), rq,
                             axis=0) for t in (k, v))
        att = flash_attention(qr[None], kr[None], vr[None], True, sm_scale,
                              256, cfg.window if kind == "swa" else None,
                              first_key)
        return (att[0].transpose(1, 0, 2).reshape(
            1, S, cfg.num_heads // 2, 2, w), st)

    def recur_kda(i, qkv, gb, st):
        """The conv from an empty history, the chunkwise delta rule from an
        empty state; the prompt's last state and conv tail into the slot."""
        p, li, kt = "layer%d" % i, ssm_at[i], cfg.kda_conv
        xp = jnp.pad(qkv[0], ((kt - 1, 0), (0, 0)))         # (S + K - 1, C)
        xc = _kda_conv([xp[t:t + S] for t in range(kt)],
                       params[p + "_kda_conv_weight"]).astype(qkv.dtype)
        q, k, v = _kda_heads(xc, cfg)
        o, ssm = kda_chunk(q, k, v, gb[0][0], gb[1][0], length, st["ssm"],
                           slot, li)
        tail = jax.lax.dynamic_slice_in_dim(xp, length, kt - 1, axis=0)
        st = dict(st, ssm=ssm, conv=st["conv"].at[li, slot].set(
            tail.reshape(-1).astype(st["conv"].dtype)))
        return None, o[None], st

    def recur(i, xin, z, st):
        if cfg.kinds()[i] == "kda":
            return recur_kda(i, xin, z, st)
        p, li = "layer%d" % i, ssm_at[i]
        # causal depthwise conv over time from an empty history
        xp = jnp.pad(xin[0], ((taps - 1, 0), (0, 0)))       # (S + K - 1, Dn)
        window = jnp.stack([xp[t:t + S] for t in range(taps)], axis=1)
        xc = _conv_taps(window, params, p).astype(xin.dtype)
        dt, b, c = _ssm_inputs(xc, params, p, cfg, prec)
        a = -jnp.exp(params[p + "_ssm_a_log"].astype(jnp.float32))
        y, out, h = ssm_scan(xc, dt, a, b, c, params[p + "_ssm_d"], z[0],
                             jnp.zeros(a.shape, jnp.float32), length)
        # the K - 1 inputs before position `length`
        tail = jax.lax.dynamic_slice_in_dim(xp, length, taps - 1, axis=0)
        st = dict(st,
                  conv=st["conv"].at[li, slot].set(
                      tail.reshape(-1).astype(st["conv"].dtype)),
                  ssm=st["ssm"].at[li, slot].set(h))
        return y[None], out[None], st

    x = _embed(params, tokens, table_pos, cfg)                 # (1, S, M)
    state = {"k": k_pages, "v": v_pages, "wk": aux["wk"], "wv": aux["wv"],
             "conv": aux["conv"], "ssm": aux["ssm"]}
    x, state, loads = _layers(x, params, cfg, prec, positions, valid(),
                              attend, state, recur)
    logits = _head(_last_rows(x, last), params, cfg, prec)     # (P, V)
    next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (next_token, logits, state["k"], state["v"],
            {k: state[k] for k in ("wk", "wv", "conv", "ssm")}) + loads


def _paged_step(params, tokens, positions, block_tables, context_lens,
                k_pages, v_pages, cfg, aux=None):
    """:func:`decode` and :func:`extend` are one function of ``tokens``
    (B, T): T = 1 lane is the decode step, T = k + 1 the verify pass.
    Every lane writes its K/V into the stream's blocks first (distinct
    slots per lane; overflow lanes pile into trash), then reads back under
    its OWN context length — lane t cannot see lanes > t."""
    import jax.numpy as jnp

    if cfg.hybrid:
        return _paged_step_hybrid(params, tokens, positions, block_tables,
                                  context_lens, k_pages, v_pages, cfg, aux)
    B, T = tokens.shape
    hh, hd = cfg.num_heads, cfg.head_dim
    bs, rows, lanes = k_pages.shape[2:]
    prec = fp32_precision(k_pages.dtype)

    in_range = positions < cfg.max_len                          # (B, T)
    safe_pos = jnp.minimum(positions, cfg.max_len - 1)
    page_ids = jnp.take_along_axis(block_tables, safe_pos // bs, axis=1)
    page_ids = jnp.where(in_range, page_ids, 0)  # overflow -> trash block
    slots = jnp.where(in_range, safe_pos % bs, 0)
    # a live stream's first block is a real one; padded batch rows carry
    # all-trash tables
    valid = in_range & (block_tables[:, :1] > 0)

    def attend(i, q, k_new, v_new, pages, r=None):
        kp, vp = pages
        ids, tables = page_ids, block_tables
        if r is not None:
            # a looped model's pass r: its own part of the pool, found
            # through the table (block 0 of a part is that pass's trash)
            part = r * (kp.shape[1] // cfg.loop_steps)
            ids, tables = page_ids + part, block_tables + part
        kp = kp.at[i, ids.reshape(-1), slots.reshape(-1)].set(
            k_new.reshape(B * T, rows, lanes).astype(kp.dtype))
        vp = vp.at[i, ids.reshape(-1), slots.reshape(-1)].set(
            v_new.reshape(B * T, rows, lanes).astype(vp.dtype))
        attn = paged_attention_multi(q.reshape(B, T, hh, hd), kp, vp,
                                     tables, context_lens, layer=i)
        return attn.reshape(B, T, hh * hd), (kp, vp)

    x = _embed(params, tokens, safe_pos, cfg)                   # (B, T, M)
    x, (k_pages, v_pages), loads = _layers(
        x, params, cfg, prec, safe_pos, valid, attend, (k_pages, v_pages))

    logits = _head(x.reshape(B * T, cfg.model_dim), params, cfg,
                   prec).reshape(B, T, -1)                      # (B, T, V)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # overflow contract: poison the overflowed lanes, loudly
    next_tokens = jnp.where(in_range, next_tokens, -1)
    logits = jnp.where(in_range[:, :, None], logits,
                       jnp.asarray(np.nan, logits.dtype))
    return (next_tokens, logits, k_pages, v_pages) + loads


def _paged_step_hybrid(params, tokens, positions, block_tables,
                       context_lens, k_pages, v_pages, cfg, aux):
    """:func:`_paged_step` for a model with ``layer_kinds``, one token a
    stream. ``aux``: ``wtables`` (B, nb) the streams' window-pool blocks by
    position (0 behind the window), ``slots`` (B,) their state slots (0,
    the trash slot, for padded rows), and the donated ``wk`` / ``wv`` /
    ``conv`` / ``ssm``. A "swa" layer writes into and reads from the window
    pool, from the block that holds ``context_len - window``; the "full"
    layer writes the full pool and the "cross" layers read it; a "mamba"
    layer shifts its conv tail and updates its state in the stream's slot.
    The four query heads of a K/V row ride as four query lanes of the
    paged kernel. Returns :func:`_paged_step`'s four and the dict of the
    four arrays."""
    import jax.numpy as jnp

    B, T = tokens.shape
    if T != 1:
        raise ValueError("a model with state or window layers decodes one "
                         "token a step (no verify pass)")
    full, win = cfg.cache_specs()
    hm = full.head_major
    bs = k_pages.shape[full.block_axis]
    g, w = full.k_rows
    rq = cfg.num_heads // g
    prec = fp32_precision(k_pages.dtype)
    sm_scale = 1.0 / float(np.sqrt(cfg.head_dim))
    full_at, win_at, ssm_at = _cache_layers(cfg)
    wtables, slots = aux["wtables"], aux["slots"]
    taps = cfg.ssm_conv

    in_range = positions < cfg.max_len                          # (B, 1)
    safe_pos = jnp.minimum(positions, cfg.max_len - 1)
    at = jnp.where(in_range, safe_pos % bs, 0).reshape(-1)
    valid = in_range & (block_tables[:, :1] > 0)
    lanes_ctx = jnp.repeat(context_lens, rq, axis=1)            # (B, R)

    def page_ids(tables):
        ids = jnp.take_along_axis(tables, safe_pos // bs, axis=1)
        return jnp.where(in_range, ids, 0).reshape(-1)  # overflow -> trash

    def write(pages, li, ids, new, rows=full.k_rows):
        g, w = rows
        new = new.reshape(B, g, w).astype(pages.dtype)
        if hm:
            # every (page, row, slot) named: the scatter's windows are the
            # 128-lane rows themselves, which it writes where they lie (a
            # (G, 1, W) window through the block is relaid out first: two
            # copies of the pool a layer)
            return pages.at[li, ids[:, None], jnp.arange(g)[None],
                            at[:, None]].set(new)
        return pages.at[li, ids, at].set(new)

    def attend_mla(i, q, c, kr, st):
        """Cache this token's latent and rotated key, then the ABSORBED
        form, the published arithmetic re-associated: a head's query goes
        through ``W_uk`` (``head_dim`` -> ``kv_rank``) and reads the cached
        latents as they lie, all heads ONE row a token; the heads'
        ``kv_rank``-wide results go through ``W_uv``."""
        li = full_at[i]
        ids = page_ids(block_tables)
        vrows = full.v_rows
        st = dict(st, k=write(st["k"], li, ids, c),
                  v=write(st["v"], li, ids, _pad_lanes(kr, vrows[1]), vrows))
        dn, dv = cfg.head_dim, cfg.v_dim
        # W_ukv by head, (H, dn + dv, kv_rank), taken WHOLE on both sides —
        # the query's value lanes are zeros, the result's key lanes are
        # dropped — so that no step slices (and so copies) the weight
        w = params["layer%d_mla_kv_up_weight" % i].reshape(
            cfg.num_heads, dn + dv, cfg.kv_rank)
        qn, qr = (t[:, 0] for t in q)                   # (B, H, dn) / dr
        qc = jnp.einsum("bhe,hec->bhc", _pad_lanes(qn, dn + dv), w,
                        precision=prec)
        out = latent_paged(qc, _pad_lanes(qr, vrows[1]), st["k"], st["v"],
                           block_tables, context_lens[:, 0],
                           mla_sm_scale(cfg), layer=li)  # (B, H, kv_rank)
        out = jnp.einsum("bhc,hec->bhe", out, w, precision=prec)[..., dn:]
        return out.reshape(B, 1, cfg.num_heads * dv), st

    def attend_gqa(i, q, k, v, st):
        """Write this token's K and V rows, then the ONE paged kernel: the
        R = H / Hkv query heads of a K/V row ride as its R query lanes
        (one context length a stream), V pages narrower than K pages, the
        sink as the online softmax's start state; a "swa" layer's walk and
        a "full" layer's are two ops on a trace."""
        kind = cfg.kinds()[i]
        if kind == "swa":
            kp, vp, tables, li, window, spec = ("wk", "wv", wtables,
                                                win_at[i], cfg.window, win)
        else:
            kp, vp, tables, li, window, spec = ("k", "v", block_tables,
                                                full_at[i], None, full)
        (hk, wk), vrows = spec.k_rows, spec.v_rows
        r = cfg.num_heads // hk
        ids = page_ids(tables)
        st = dict(st, **{
            kp: write(st[kp], li, ids, _pad_lanes(k, wk), (hk, wk)),
            vp: write(st[vp], li, ids, _pad_lanes(v, vrows[1]), vrows)})
        # (B, R, Hkv, W): query head g R + r is lane r of K/V row g
        qr = _pad_lanes(q[:, 0], wk).reshape(B, hk, r, wk).transpose(
            0, 2, 1, 3)
        sink = _sink(params, i, cfg)
        att = paged_attention_multi(
            qr, st[kp], st[vp], tables, context_lens, sm_scale=sm_scale,
            layer=li, window=window, head_major=True,
            sink=None if sink is None else sink.reshape(hk, r).T,
            name="paged_window_walk" if kind == "swa" else "paged_full_walk")
        att = att.transpose(0, 2, 1, 3).reshape(B, 1, cfg.num_heads,
                                                vrows[1])
        return att[..., :cfg.v_dim], st

    def attend(i, q, k, v, st):
        kind = cfg.kinds()[i]
        if kind == "mla":
            return attend_mla(i, q, k, v, st)
        if cfg.gqa:
            return attend_gqa(i, q, k, v, st)
        if kind == "swa":
            kp, vp, tables, li, window = ("wk", "wv", wtables, win_at[i],
                                          cfg.window)
        else:
            full = i if kind == "full" else max(
                j for j in cfg.layers_of("full") if j < i)
            kp, vp, tables, li, window = ("k", "v", block_tables,
                                          full_at[full], None)
        if kind != "cross":
            ids = page_ids(tables)
            st = dict(st, **{kp: write(st[kp], li, ids, k),
                             vp: write(st[vp], li, ids, v)})
        # (B, R, G, W): the R query heads of a K/V row as R query lanes
        qr = _diff_q_rows(q, cfg)[:, 0].transpose(0, 2, 1, 3)
        att = paged_attention_multi(qr, st[kp], st[vp], tables, lanes_ctx,
                                    sm_scale=sm_scale, layer=li,
                                    window=window, head_major=hm)
        return (att.transpose(0, 2, 1, 3).reshape(
            B, 1, cfg.num_heads // 2, 2, w), st)

    def recur_kda(i, qkv, gb, st):
        """Shift the stream's conv tail, one delta-rule update of its state
        where it lies."""
        p, li, kt = "layer%d" % i, ssm_at[i], cfg.kda_conv
        tail = st["conv"][li, slots].reshape(B, kt - 1, -1)
        window = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
        xc = _kda_conv([window[:, t] for t in range(kt)],
                       params[p + "_kda_conv_weight"]).astype(qkv.dtype)
        q, k, v = _kda_heads(xc, cfg)
        o, ssm = kda_step(q, k, v, gb[0][:, 0], gb[1][:, 0], st["ssm"],
                          slots, li)
        st = dict(st, ssm=ssm, conv=st["conv"].at[li, slots].set(
            window[:, 1:].reshape(B, -1).astype(st["conv"].dtype)))
        return None, o[:, None], st

    def recur(i, xin, z, st):
        if cfg.kinds()[i] == "kda":
            return recur_kda(i, xin, z, st)
        p, li = "layer%d" % i, ssm_at[i]
        tail = st["conv"][li, slots].reshape(B, taps - 1, -1)
        window = jnp.concatenate([tail.astype(xin.dtype), xin], axis=1)
        xc = _conv_taps(window, params, p).astype(xin.dtype)     # (B, Dn)
        dt, b, c = _ssm_inputs(xc, params, p, cfg, prec)
        a = -jnp.exp(params[p + "_ssm_a_log"].astype(jnp.float32))
        y, out, ssm = ssm_step(xc, dt, a, b, c, params[p + "_ssm_d"],
                               z[:, 0], st["ssm"], slots, li)
        st = dict(st, ssm=ssm, conv=st["conv"].at[li, slots].set(
            window[:, 1:].reshape(B, -1).astype(st["conv"].dtype)))
        return y[:, None], out[:, None], st

    x = _embed(params, tokens, safe_pos, cfg)                   # (B, 1, M)
    state = {"k": k_pages, "v": v_pages, "wk": aux["wk"], "wv": aux["wv"],
             "conv": aux["conv"], "ssm": aux["ssm"]}
    x, state, loads = _layers(x, params, cfg, prec, safe_pos, valid, attend,
                              state, recur)
    logits = _head(x.reshape(B, cfg.model_dim), params, cfg,
                   prec).reshape(B, 1, -1)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    next_tokens = jnp.where(in_range, next_tokens, -1)
    logits = jnp.where(in_range[:, :, None], logits,
                       jnp.asarray(np.nan, logits.dtype))
    return (next_tokens, logits, state["k"], state["v"],
            {k: state[k] for k in ("wk", "wv", "conv", "ssm")}) + loads


def decode(params, tokens, positions, block_tables, context_lens,
           k_pages, v_pages, cfg, aux=None):
    """The fused paged decode step: one token for every sequence in the
    padded batch, one XLA program per batch bucket.

    tokens:       (B,) int32 — each stream's pending input token
    positions:    (B,) int32 — the slot this token is written at
                  (== tokens cached so far for the stream)
    block_tables: (B, max_len // block_size) int32 — pool blocks per
                  stream in position order; unused/padded entries = 0
    context_lens: (B,) int32 — valid tokens AFTER this step's write
                  (positions + 1 for live rows; padded rows pass 1)
    k/v_pages:    pool pages (donated)

    Returns ``(next_tokens (B,), logits (B, V), k_pages, v_pages)``, and
    the per-layer ``tokens_per_expert`` (L, E) as a fifth result for a
    config with experts. Out-of-range positions (>= max_len) honor the
    overflow contract: the write is routed to the trash block,
    ``next_token`` is -1, and the lane's logits are NaN — the cache cannot
    be corrupted from the graph. A model with ``layer_kinds`` takes ``aux``
    and returns its arrays as a fifth result (:func:`_paged_step_hybrid`).
    """
    nxt, logits, *rest = _paged_step(
        params, tokens[:, None], positions[:, None], block_tables,
        context_lens[:, None], k_pages, v_pages, cfg, aux)
    return (nxt[:, 0], logits[:, 0]) + tuple(rest)


def decode_chunk(params, tokens, positions, block_tables, context_lens,
                 steps_left, eos, n, k_pages, v_pages, cfg, chunk, aux=None):
    """Up to ``chunk`` :func:`decode` steps in ONE program: a loop on the
    device with a TRACED trip count ``n`` (1 <= n <= chunk; data, so one
    executable serves every n) whose body is the decode step, each step's
    greedy token fed to the next. The host dispatches once and fetches once
    a chunk; ``n = 1`` with every lane's ``steps_left`` 1 is the single
    step.

    tokens, positions, block_tables, context_lens: as :func:`decode`, for
                  the chunk's FIRST step
    steps_left:   (B,) int32 — steps each lane may still take (its length
                  cap; 0 for a padded row)
    eos:          (B,) int32 — the lane's end-of-sequence id, -1 for none
    n:            () int32 — steps this dispatch runs
    chunk:        static: the rows of the results

    A lane is live while its ``steps_left`` lasts, it has produced no
    ``eos`` and its next position is below ``max_len``. After its last step
    it is DEAD for the rest of the chunk, which is a padded row's contract:
    its table row reads all trash (block 0; slot 0 of the state slots), so
    it writes nothing a live stream reads, ``valid`` is false so the
    experts' load does not count it, and its result token is -1.

    Returns ``(next_tokens (chunk, B)`` — row j the tokens of step j, -1
    for a dead lane and for rows >= n —, ``logits (B, V)`` of each lane's
    last live step, ``k_pages, v_pages)`` and a fifth result as
    :func:`decode`: the experts' load PER STEP ``(chunk, L, E)``, or a
    model with ``layer_kinds``' arrays (then the load, if it has experts
    too, is a sixth)."""
    import jax
    import jax.numpy as jnp

    B = tokens.shape[0]
    names = ("wk", "wv", "conv", "ssm") if cfg.hybrid else ()
    state = {"j": jnp.int32(0), "tokens": tokens, "positions": positions,
             "context_lens": context_lens, "left": steps_left,
             "out": jnp.full((chunk, B), -1, jnp.int32),
             "logits": jnp.zeros((B, cfg.vocab_size), jnp.float32),
             "caches": dict({k: aux[k] for k in names}, k=k_pages,
                            v=v_pages)}
    if cfg.num_experts:
        state["loads"] = jnp.zeros(
            (chunk, cfg.expert_layers, cfg.num_experts), jnp.int32)

    def body(s):
        alive = s["left"] > 0

        def live(a, dead):
            return jnp.where(alive.reshape((B,) + (1,) * (a.ndim - 1)), a,
                             dead)

        caches = s["caches"]
        step_aux = dict({k: caches[k] for k in names},
                        wtables=live(aux["wtables"], 0),
                        slots=live(aux["slots"], 0)) if cfg.hybrid else None
        nxt, logits, kp, vp, *rest = decode(
            params, live(s["tokens"], 0), live(s["positions"], 0),
            live(block_tables, 0), live(s["context_lens"], 1), caches["k"],
            caches["v"], cfg, step_aux)
        s = dict(s, caches=dict(rest[0] if cfg.hybrid else {}, k=kp, v=vp),
                 out=s["out"].at[s["j"]].set(live(nxt, -1)),
                 logits=live(logits, s["logits"]))
        if cfg.num_experts:
            s["loads"] = s["loads"].at[s["j"]].set(rest[-1])
        ended = ((nxt == eos) & (eos >= 0)) \
            | (s["positions"] + 1 >= cfg.max_len)
        return dict(s, j=s["j"] + 1, tokens=live(nxt, s["tokens"]),
                    positions=s["positions"] + alive,
                    context_lens=s["context_lens"] + alive,
                    left=jnp.where(alive & ~ended, s["left"] - 1, 0))

    n = jnp.minimum(n, chunk)       # a row past the results is no row
    s = jax.lax.while_loop(lambda s: s["j"] < n, body, state)
    caches = s["caches"]
    more = (({k: caches[k] for k in names},) if cfg.hybrid else ()) \
        + ((s["loads"],) if cfg.num_experts else ())
    return (s["out"], s["logits"], caches["k"], caches["v"]) + more


def extend(params, tokens, positions, block_tables, context_lens,
           k_pages, v_pages, cfg):
    """The speculative-decoding VERIFY step: :func:`decode` generalized to
    T tokens per stream, scored in ONE multi-query paged-attention pass.

    tokens:       (B, T) int32 — lane 0 is the stream's pending token,
                  lanes 1..T-1 the draft's proposals
    positions:    (B, T) int32 — each lane's write slot (consecutive:
                  context_len + lane for live rows)
    block_tables: (B, max_len // block_size) int32 — ONE table per stream
                  (the window's lanes share the stream's blocks)
    context_lens: (B, T) int32 — valid tokens PER LANE after this step's
                  writes (positions + 1 for live lanes) — per-lane
                  masking is what makes the window causal
    k/v_pages:    pool pages (donated)

    Returns ``(next_tokens (B, T), logits (B, T, V), k_pages, v_pages)``
    (+ the experts' load, as :func:`decode`): lane t's output is the
    target model's greedy next token given the stream's context plus
    window lanes 0..t — exactly what :func:`decode` would have produced
    had the window been fed one token at a time, so greedy acceptance of
    matching draft proposals emits a token stream bit-identical to
    target-only decoding. Out-of-range lanes (position >= max_len) honor
    the overflow contract per lane: write routed to the trash block,
    token -1, logits NaN.
    """
    return _paged_step(params, tokens, positions, block_tables,
                       context_lens, k_pages, v_pages, cfg)
