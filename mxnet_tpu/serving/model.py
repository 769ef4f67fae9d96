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
(``KVBlockPool.page_shape``; ``(H, D)`` rows are the r = 1 case): new K/V
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
import numpy as np

from ..ops.attention import flash_attention, paged_attention_multi
from ..ops.moe import moe_ffn
from ..ops.registry import fp32_precision

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

    ``max_len`` bounds every stream's total length (the position table's
    rows, or the positions the rotary model was trained for)."""

    __slots__ = ("vocab_size", "num_layers", "model_dim", "num_heads",
                 "ffn_dim", "max_len", "norm", "pos", "rope_theta",
                 "qk_norm", "head_dim", "num_experts", "experts_per_tok",
                 "bias")

    def __init__(self, vocab_size=32000, num_layers=4, model_dim=256,
                 num_heads=4, ffn_dim=1024, max_len=128, norm="layer",
                 pos="learned", rope_theta=10000.0, qk_norm=False,
                 head_dim=None, num_experts=0, experts_per_tok=0, bias=True):
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
        if self.norm not in ("layer", "rms"):
            raise ValueError("norm must be 'layer' or 'rms', not %r" % norm)
        if self.pos not in ("learned", "rope"):
            raise ValueError("pos must be 'learned' or 'rope', not %r" % pos)
        if self.pos == "rope" and self.head_dim % 2:
            raise ValueError("rotary position needs an even head_dim")
        if self.num_experts and not (
                1 <= self.experts_per_tok <= self.num_experts):
            raise ValueError("experts_per_tok must be in 1..num_experts")

    def key(self):
        return tuple(getattr(self, k) for k in ModelConfig.__slots__)

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
    shapes = {"embed_weight": (v, m), "final_ln_gamma": (1, 1, m),
              "lm_head_weight": (v, m)}
    if cfg.pos == "learned":
        shapes["pos_embed_weight"] = (1, cfg.max_len, m)
    if cfg.norm == "layer":
        shapes["final_ln_beta"] = (1, 1, m)
    if cfg.bias:
        shapes["lm_head_bias"] = (v,)
    for i in range(cfg.num_layers):
        p = "layer%d" % i
        shapes.update({
            p + "_ln1_gamma": (1, 1, m), p + "_ln2_gamma": (1, 1, m),
            p + "_attn_in_weight": (3 * hm, m),
            p + "_attn_out_weight": (m, hm),
        })
        if cfg.norm == "layer":
            shapes.update({p + "_ln1_beta": (1, 1, m),
                           p + "_ln2_beta": (1, 1, m)})
        if cfg.qk_norm:
            shapes.update({p + "_q_norm_gamma": (hm,),
                           p + "_k_norm_gamma": (hm,)})
        if cfg.num_experts:
            e = cfg.num_experts
            shapes.update({p + "_router_weight": (e, m),
                           p + "_experts_gate_weight": (e, f, m),
                           p + "_experts_up_weight": (e, f, m),
                           p + "_experts_down_weight": (e, m, f)})
        else:
            shapes.update({p + "_ffn1_weight": (f, m),
                           p + "_ffn2_weight": (m, f)})
            if cfg.bias:
                shapes.update({p + "_ffn1_bias": (f,),
                               p + "_ffn2_bias": (m,)})
    return shapes


def random_params(cfg, seed=0, dtype=np.float32):
    """Deterministic host-side random weights (gamma=1, beta/bias=0,
    weights ~N(0, 0.02)) — the same function call in any process yields
    byte-identical params, which is what lets the e2e test compare a
    served subprocess against an in-process sequential reference."""
    rng = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(param_shapes(cfg).items()):
        if name.endswith("_gamma"):
            out[name] = np.ones(shape, dtype)
        elif name.endswith(("_beta", "_bias")):
            out[name] = np.zeros(shape, dtype)
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
        return ModelConfig(*cfg.key())
    if spec not in SERVING_DRAFT_PRESETS:
        raise ValueError(
            "unknown draft model %r: expected 'self' or one of %s "
            "(models/transformer_lm.py SERVING_DRAFT_PRESETS)"
            % (spec, sorted(SERVING_DRAFT_PRESETS)))
    p = SERVING_DRAFT_PRESETS[spec]
    return ModelConfig(cfg.vocab_size, p["num_layers"], p["model_dim"],
                       p["num_heads"], p["ffn_dim"], cfg.max_len)


_NORM_EPS = 1e-5


def _layer_norm(x, gamma, beta):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _NORM_EPS) * gamma + beta


def _rms_norm(x, gamma):
    """t / sqrt(mean(t^2) + eps) * gamma, statistics in float32."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + _NORM_EPS) * gamma.astype(jnp.float32)
    return y.astype(x.dtype)


def _norm(x, params, name, cfg):
    if cfg.norm == "rms":
        return _rms_norm(x, params[name + "_gamma"])
    return _layer_norm(x, params[name + "_gamma"], params[name + "_beta"])


def _rope(x, positions, cfg):
    """Rotate-half rotary position on ``x`` (A, B, H*hd) at absolute
    ``positions`` (A, B): the first and second half of a head are paired,
    angle = position * theta^(-2i/hd). Computed in float32."""
    import jax.numpy as jnp

    a, b, _ = x.shape
    hd = cfg.head_dim
    half = hd // 2
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)                 # (A, B, 1, half)
    x4 = x.reshape(a, b, cfg.num_heads, hd).astype(jnp.float32)
    x1, x2 = x4[..., :half], x4[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


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
    if cfg.pos != "learned":
        return x
    if positions is None:
        return x + params["pos_embed_weight"][:, :tokens.shape[1]]
    pos_tab = params["pos_embed_weight"].reshape(cfg.max_len, cfg.model_dim)
    return x + jnp.take(pos_tab, positions, axis=0)


def _ffn(x2d, params, prefix, cfg, prec):
    import jax.numpy as jnp

    f = jnp.dot(x2d, params[prefix + "_ffn1_weight"].T, precision=prec)
    if cfg.bias:
        f = f + params[prefix + "_ffn1_bias"]
    f = jnp.dot(jnp.maximum(f, 0), params[prefix + "_ffn2_weight"].T,
                precision=prec)
    return f + params[prefix + "_ffn2_bias"] if cfg.bias else f


def _head(x2d, params, cfg, prec):
    """Final-norm'd rows (R, M) -> logits (R, V), float32 whatever the
    weights' type."""
    import jax.numpy as jnp

    logits = jnp.dot(x2d, params["lm_head_weight"].T, precision=prec,
                     preferred_element_type=jnp.float32)
    return logits + params["lm_head_bias"] if cfg.bias else logits


def _layer(x, params, i, cfg, prec, positions, valid, attend, state):
    """THE layer body, shared by :func:`prefill`, :func:`decode` and
    :func:`extend`: norm -> q/k/v projections (QK-norm, position) ->
    ``attend`` -> output projection -> norm -> FFN or routed experts.

    x:         (A, B, M) — (1, S, M) in prefill, (B, 1, M) in decode,
               (B, T, M) in the verify pass
    positions: (A, B) int32 absolute positions (rotary models read them)
    valid:     (A, B) bool — live lanes, for the experts' load count
    attend:    ``(i, q, k, v, state) -> (attn (A, B, H*hd), state)``: what
               differs between the three steps — where this layer's K and
               V go and which attention reads them. ``state`` is the
               caller's (the pages, or the K/V collected so far).

    Returns ``(x, state, tokens_per_expert (E,) or None)``."""
    import jax.numpy as jnp

    p = "layer%d" % i
    h = _norm(x, params, p + "_ln1", cfg)
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
    x = x + attn
    h = _norm(x, params, p + "_ln2", cfg)
    a, b, m = x.shape
    load = None
    if cfg.num_experts:
        f, load = moe_ffn(
            h.reshape(a * b, m), params[p + "_router_weight"],
            params[p + "_experts_gate_weight"],
            params[p + "_experts_up_weight"],
            params[p + "_experts_down_weight"], cfg.experts_per_tok,
            valid=valid.reshape(a * b))
    else:
        f = _ffn(h.reshape(a * b, m), params, p, cfg, prec)
    return x + f.reshape(a, b, m), state, load


def _layers(x, params, cfg, prec, positions, valid, attend, state):
    """Every layer in turn: ``(x, state, loads)`` with ``loads`` the
    per-layer ``tokens_per_expert`` stacked (L, E), or () without
    experts."""
    import jax.numpy as jnp

    loads = []
    for i in range(cfg.num_layers):
        x, state, load = _layer(x, params, i, cfg, prec, positions, valid,
                                attend, state)
        loads.append(load)
    return x, state, ((jnp.stack(loads),) if cfg.num_experts else ())


def prefill(params, tokens, length, block_table, k_pages, v_pages, cfg):
    """Full-sequence prefill for ONE request at a padded bucket length.

    tokens:      (1, S) int32, S a bucket multiple of the pool block size
                 (prompt left-aligned, tail padded with 0s)
    length:      () int32 — true prompt length (1 <= length <= S)
    block_table: (S // block_size,) int32 — the request's allocated blocks
                 in position order; tail entries past the prompt = 0 (trash)
    k/v_pages:   the pool pages, (L, N, bs, G, W) — donated by the engine

    Returns ``(next_token (1,) int32, logits (1, V), k_pages, v_pages)``
    — and, for a config with experts, a fifth result: the per-layer
    ``tokens_per_expert`` (L, E) int32 of the ``length`` live tokens.
    Every layer's K/V for positions < S is scattered into the pool through
    the table, and the greedy next token sampled at position
    ``length - 1``. Attention is the training block's
    ``flash_attention(causal=True)`` — padded tail rows compute garbage
    but cannot reach rows < length (causal mask) and their cache writes
    land in trash-table blocks.
    """
    import jax.numpy as jnp

    _, S = tokens.shape
    hh, hd = cfg.num_heads, cfg.head_dim
    bs, rows, lanes = k_pages.shape[2:]
    prec = fp32_precision(k_pages.dtype)
    positions = jnp.arange(S, dtype=jnp.int32)[None]

    def split_heads(t):
        return t.reshape(1, S, hh, hd).transpose(0, 2, 1, 3)   # (1, H, S, hd)

    def attend(i, q, k, v, kv):
        attn = flash_attention(split_heads(q), split_heads(k),
                               split_heads(v), True)
        return (attn.transpose(0, 2, 1, 3).reshape(1, S, hh * hd),
                (kv[0] + (k,), kv[1] + (v,)))

    x = _embed(params, tokens, None, cfg)                      # (1, S, M)
    x, (k_all, v_all), loads = _layers(
        x, params, cfg, prec, positions, positions < length, attend,
        ((), ()))

    # scatter every layer's K/V through the block table (trash entries
    # absorb the padded tail)
    kw = jnp.stack(k_all).reshape(cfg.num_layers, S // bs, bs, rows, lanes)
    vw = jnp.stack(v_all).reshape(cfg.num_layers, S // bs, bs, rows, lanes)
    k_pages = k_pages.at[:, block_table].set(kw.astype(k_pages.dtype))
    v_pages = v_pages.at[:, block_table].set(vw.astype(v_pages.dtype))

    x = _norm(x, params, "final_ln", cfg)
    h_last = jnp.take(x[0], length - 1, axis=0)                # (M,)
    logits = _head(h_last[None], params, cfg, prec)            # (1, V)
    next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (next_token, logits, k_pages, v_pages) + loads


def _paged_step(params, tokens, positions, block_tables, context_lens,
                k_pages, v_pages, cfg):
    """:func:`decode` and :func:`extend` are one function of ``tokens``
    (B, T): T = 1 lane is the decode step, T = k + 1 the verify pass.
    Every lane writes its K/V into the stream's blocks first (distinct
    slots per lane; overflow lanes pile into trash), then reads back under
    its OWN context length — lane t cannot see lanes > t."""
    import jax.numpy as jnp

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

    def attend(i, q, k_new, v_new, pages):
        kp, vp = pages
        kp = kp.at[i, page_ids.reshape(-1), slots.reshape(-1)].set(
            k_new.reshape(B * T, rows, lanes).astype(kp.dtype))
        vp = vp.at[i, page_ids.reshape(-1), slots.reshape(-1)].set(
            v_new.reshape(B * T, rows, lanes).astype(vp.dtype))
        attn = paged_attention_multi(q.reshape(B, T, hh, hd), kp, vp,
                                     block_tables, context_lens, layer=i)
        return attn.reshape(B, T, hh * hd), (kp, vp)

    x = _embed(params, tokens, safe_pos, cfg)                   # (B, T, M)
    x, (k_pages, v_pages), loads = _layers(
        x, params, cfg, prec, safe_pos, valid, attend, (k_pages, v_pages))

    x = _norm(x, params, "final_ln", cfg)
    logits = _head(x.reshape(B * T, cfg.model_dim), params, cfg,
                   prec).reshape(B, T, -1)                      # (B, T, V)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # overflow contract: poison the overflowed lanes, loudly
    next_tokens = jnp.where(in_range, next_tokens, -1)
    logits = jnp.where(in_range[:, :, None], logits,
                       jnp.asarray(np.nan, logits.dtype))
    return (next_tokens, logits, k_pages, v_pages) + loads


def decode(params, tokens, positions, block_tables, context_lens,
           k_pages, v_pages, cfg):
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
    be corrupted from the graph.
    """
    nxt, logits, *rest = _paged_step(
        params, tokens[:, None], positions[:, None], block_tables,
        context_lens[:, None], k_pages, v_pages, cfg)
    return (nxt[:, 0], logits[:, 0]) + tuple(rest)


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
