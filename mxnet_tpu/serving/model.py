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

from ..ops.attention import (flash_attention, paged_attention,
                             paged_attention_multi)
from ..ops.registry import fp32_precision

#: parameter init scale matching models/transformer_lm.py's Normal(0.02)
#: pos-embed init; used by random_params for self-contained serving runs
_INIT_SCALE = 0.02


class ModelConfig:
    """Static Transformer-LM shape config (hashable: feeds compileobs
    graph keys). ``max_len`` is the training graph's ``seq_len`` — the
    position-embedding table bounds every stream's total length."""

    __slots__ = ("vocab_size", "num_layers", "model_dim", "num_heads",
                 "ffn_dim", "max_len")

    def __init__(self, vocab_size=32000, num_layers=4, model_dim=256,
                 num_heads=4, ffn_dim=1024, max_len=128):
        self.vocab_size = int(vocab_size)
        self.num_layers = int(num_layers)
        self.model_dim = int(model_dim)
        self.num_heads = int(num_heads)
        self.ffn_dim = int(ffn_dim)
        self.max_len = int(max_len)
        if self.model_dim % self.num_heads:
            raise ValueError("model_dim must divide by num_heads")

    def key(self):
        return (self.vocab_size, self.num_layers, self.model_dim,
                self.num_heads, self.ffn_dim, self.max_len)

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
    """Name -> shape for every weight the serving forward consumes —
    exactly the training graph's ``arg_dict`` names (minus data/label)."""
    m, f, v = cfg.model_dim, cfg.ffn_dim, cfg.vocab_size
    shapes = {
        "embed_weight": (v, m),
        "pos_embed_weight": (1, cfg.max_len, m),
        "final_ln_gamma": (1, 1, m),
        "final_ln_beta": (1, 1, m),
        "lm_head_weight": (v, m),
        "lm_head_bias": (v,),
    }
    for i in range(cfg.num_layers):
        p = "layer%d" % i
        shapes.update({
            p + "_ln1_gamma": (1, 1, m), p + "_ln1_beta": (1, 1, m),
            p + "_ln2_gamma": (1, 1, m), p + "_ln2_beta": (1, 1, m),
            p + "_attn_in_weight": (3 * m, m),
            p + "_attn_out_weight": (m, m),
            p + "_ffn1_weight": (f, m), p + "_ffn1_bias": (f,),
            p + "_ffn2_weight": (m, f), p + "_ffn2_bias": (m,),
        })
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
        a = a.data if hasattr(a, "data") and hasattr(a, "asnumpy") else a
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
    ``SERVING_DRAFT_PRESETS`` entry (a tiny zoo shape). vocab_size and
    max_len always follow the target: the draft proposes tokens from the
    same vocabulary at the same absolute positions."""
    from ..models.transformer_lm import SERVING_DRAFT_PRESETS

    if spec == "self":
        return ModelConfig(cfg.vocab_size, cfg.num_layers, cfg.model_dim,
                           cfg.num_heads, cfg.ffn_dim, cfg.max_len)
    if spec not in SERVING_DRAFT_PRESETS:
        raise ValueError(
            "unknown draft model %r: expected 'self' or one of %s "
            "(models/transformer_lm.py SERVING_DRAFT_PRESETS)"
            % (spec, sorted(SERVING_DRAFT_PRESETS)))
    p = SERVING_DRAFT_PRESETS[spec]
    return ModelConfig(cfg.vocab_size, p["num_layers"], p["model_dim"],
                       p["num_heads"], p["ffn_dim"], cfg.max_len)


def _layer_norm(x, gamma, beta):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * gamma + beta


def _ffn(x2d, params, prefix, prec):
    import jax.numpy as jnp

    f = jnp.dot(x2d, params[prefix + "_ffn1_weight"].T, precision=prec)
    f = jnp.maximum(f + params[prefix + "_ffn1_bias"], 0)
    f = jnp.dot(f, params[prefix + "_ffn2_weight"].T, precision=prec)
    return f + params[prefix + "_ffn2_bias"]


def prefill(params, tokens, length, block_table, k_pages, v_pages, cfg):
    """Full-sequence prefill for ONE request at a padded bucket length.

    tokens:      (1, S) int32, S a bucket multiple of the pool block size
                 (prompt left-aligned, tail padded with 0s)
    length:      () int32 — true prompt length (1 <= length <= S)
    block_table: (S // block_size,) int32 — the request's allocated blocks
                 in position order; tail entries past the prompt = 0 (trash)
    k/v_pages:   the pool pages, (L, N, bs, G, W) — donated by the engine

    Returns ``(next_token (1,) int32, logits (1, V), k_pages, v_pages)``:
    every layer's K/V for positions < S scattered into the pool through the
    table, and the greedy next token sampled at position ``length - 1``.
    Attention is the training block's ``flash_attention(causal=True)`` —
    padded tail rows compute garbage but cannot reach rows < length (causal
    mask) and their cache writes land in trash-table blocks.
    """
    import jax.numpy as jnp

    _, S = tokens.shape
    m, hh = cfg.model_dim, cfg.num_heads
    hd = m // hh
    bs, rows, lanes = k_pages.shape[2:]
    prec = fp32_precision(k_pages.dtype)

    x = jnp.take(params["embed_weight"], tokens, axis=0)       # (1, S, M)
    x = x + params["pos_embed_weight"][:, :S]

    def split_heads(t):
        return t.reshape(1, S, hh, hd).transpose(0, 2, 1, 3)   # (1, H, S, hd)

    k_all, v_all = [], []
    for i in range(cfg.num_layers):
        p = "layer%d" % i
        h = _layer_norm(x, params[p + "_ln1_gamma"], params[p + "_ln1_beta"])
        qkv = jnp.einsum("bsm,nm->bsn", h, params[p + "_attn_in_weight"],
                         precision=prec)
        q, k, v = jnp.split(qkv, 3, axis=-1)                   # (1, S, M)
        k_all.append(k)
        v_all.append(v)
        attn = flash_attention(split_heads(q), split_heads(k),
                               split_heads(v), True)
        attn = attn.transpose(0, 2, 1, 3).reshape(1, S, m)
        attn = jnp.einsum("bsm,nm->bsn", attn,
                          params[p + "_attn_out_weight"], precision=prec)
        x = x + attn
        h = _layer_norm(x, params[p + "_ln2_gamma"], params[p + "_ln2_beta"])
        x = x + _ffn(h.reshape(S, m), params, p, prec).reshape(1, S, m)

    # scatter every layer's K/V through the block table (trash entries
    # absorb the padded tail)
    kw = jnp.stack(k_all).reshape(cfg.num_layers, S // bs, bs, rows, lanes)
    vw = jnp.stack(v_all).reshape(cfg.num_layers, S // bs, bs, rows, lanes)
    k_pages = k_pages.at[:, block_table].set(kw.astype(k_pages.dtype))
    v_pages = v_pages.at[:, block_table].set(vw.astype(v_pages.dtype))

    x = _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"])
    h_last = jnp.take(x[0], length - 1, axis=0)                # (M,)
    logits = (jnp.dot(h_last[None], params["lm_head_weight"].T,
                      precision=prec) + params["lm_head_bias"])  # (1, V)
    next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_token, logits, k_pages, v_pages


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

    Returns ``(next_tokens (B,), logits (B, V), k_pages, v_pages)``.
    Out-of-range positions (>= max_len) honor the overflow contract:
    the write is routed to the trash block, ``next_token`` is -1, and the
    lane's logits are NaN — the cache cannot be corrupted from the graph.
    """
    import jax.numpy as jnp

    B = tokens.shape[0]
    m, hh = cfg.model_dim, cfg.num_heads
    hd = m // hh
    bs, rows, lanes = k_pages.shape[2:]
    prec = fp32_precision(k_pages.dtype)

    in_range = positions < cfg.max_len
    safe_pos = jnp.minimum(positions, cfg.max_len - 1)
    page_ids = jnp.take_along_axis(block_tables, (safe_pos // bs)[:, None],
                                   axis=1)[:, 0]
    page_ids = jnp.where(in_range, page_ids, 0)  # overflow -> trash block
    slots = jnp.where(in_range, safe_pos % bs, 0)

    pos_tab = params["pos_embed_weight"].reshape(cfg.max_len, m)
    x = (jnp.take(params["embed_weight"], tokens, axis=0)
         + jnp.take(pos_tab, safe_pos, axis=0))                # (B, M)
    x = x[:, None, :]                                          # (B, 1, M)

    for i in range(cfg.num_layers):
        p = "layer%d" % i
        h = _layer_norm(x, params[p + "_ln1_gamma"], params[p + "_ln1_beta"])
        qkv = jnp.einsum("bsm,nm->bsn", h, params[p + "_attn_in_weight"],
                         precision=prec)
        q, k_new, v_new = jnp.split(qkv, 3, axis=-1)           # (B, 1, M)
        q = q.reshape(B, hh, hd)
        k_new = k_new.reshape(B, rows, lanes)
        v_new = v_new.reshape(B, rows, lanes)
        k_pages = k_pages.at[i, page_ids, slots].set(
            k_new.astype(k_pages.dtype))
        v_pages = v_pages.at[i, page_ids, slots].set(
            v_new.astype(v_pages.dtype))
        attn = paged_attention(q, k_pages, v_pages, block_tables,
                               context_lens, layer=i)          # (B, H, hd)
        attn = attn.reshape(B, 1, m)
        attn = jnp.einsum("bsm,nm->bsn", attn,
                          params[p + "_attn_out_weight"], precision=prec)
        x = x + attn
        h = _layer_norm(x, params[p + "_ln2_gamma"], params[p + "_ln2_beta"])
        x = x + _ffn(h.reshape(B, m), params, p, prec).reshape(B, 1, m)

    x = _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"])
    logits = (jnp.dot(x.reshape(B, m), params["lm_head_weight"].T,
                      precision=prec) + params["lm_head_bias"])  # (B, V)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # overflow contract: poison the overflowed lanes, loudly
    next_tokens = jnp.where(in_range, next_tokens, -1)
    logits = jnp.where(in_range[:, None], logits,
                       jnp.asarray(np.nan, logits.dtype))
    return next_tokens, logits, k_pages, v_pages


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

    Returns ``(next_tokens (B, T), logits (B, T, V), k_pages, v_pages)``:
    lane t's output is the target model's greedy next token given the
    stream's context plus window lanes 0..t — exactly what :func:`decode`
    would have produced had the window been fed one token at a time, so
    greedy acceptance of matching draft proposals emits a token stream
    bit-identical to target-only decoding. Out-of-range lanes
    (position >= max_len) honor the overflow contract per lane: write
    routed to the trash block, token -1, logits NaN.
    """
    import jax.numpy as jnp

    B, T = tokens.shape
    m, hh = cfg.model_dim, cfg.num_heads
    hd = m // hh
    bs, rows, lanes = k_pages.shape[2:]
    prec = fp32_precision(k_pages.dtype)

    in_range = positions < cfg.max_len                          # (B, T)
    safe_pos = jnp.minimum(positions, cfg.max_len - 1)
    page_ids = jnp.take_along_axis(block_tables, safe_pos // bs, axis=1)
    page_ids = jnp.where(in_range, page_ids, 0)  # overflow -> trash block
    slots = jnp.where(in_range, safe_pos % bs, 0)

    pos_tab = params["pos_embed_weight"].reshape(cfg.max_len, m)
    x = (jnp.take(params["embed_weight"], tokens, axis=0)
         + jnp.take(pos_tab, safe_pos, axis=0))                 # (B, T, M)

    for i in range(cfg.num_layers):
        p = "layer%d" % i
        h = _layer_norm(x, params[p + "_ln1_gamma"], params[p + "_ln1_beta"])
        qkv = jnp.einsum("btm,nm->btn", h, params[p + "_attn_in_weight"],
                         precision=prec)
        q, k_new, v_new = jnp.split(qkv, 3, axis=-1)            # (B, T, M)
        q = q.reshape(B, T, hh, hd)
        k_new = k_new.reshape(B, T, rows, lanes)
        v_new = v_new.reshape(B, T, rows, lanes)
        # window lanes write their K/V first (distinct slots per lane;
        # overflow lanes pile into trash), then every lane reads back
        # under its OWN context length — lane t cannot see lanes > t
        k_pages = k_pages.at[i, page_ids, slots].set(
            k_new.astype(k_pages.dtype))
        v_pages = v_pages.at[i, page_ids, slots].set(
            v_new.astype(v_pages.dtype))
        attn = paged_attention_multi(q, k_pages, v_pages, block_tables,
                                     context_lens, layer=i)
        attn = attn.reshape(B, T, m)
        attn = jnp.einsum("btm,nm->btn", attn,
                          params[p + "_attn_out_weight"], precision=prec)
        x = x + attn
        h = _layer_norm(x, params[p + "_ln2_gamma"], params[p + "_ln2_beta"])
        x = x + _ffn(h.reshape(B * T, m), params, p, prec).reshape(B, T, m)

    x = _layer_norm(x, params["final_ln_gamma"], params["final_ln_beta"])
    logits = (jnp.dot(x.reshape(B * T, m), params["lm_head_weight"].T,
                      precision=prec)
              + params["lm_head_bias"]).reshape(B, T, -1)       # (B, T, V)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # overflow contract: poison the overflowed lanes, loudly
    next_tokens = jnp.where(in_range, next_tokens, -1)
    logits = jnp.where(in_range[:, :, None], logits,
                       jnp.asarray(np.nan, logits.dtype))
    return next_tokens, logits, k_pages, v_pages
