"""Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning; the
SambaY decoder-hybrid-decoder, arXiv:2507.06607, with differential attention,
arXiv:2410.05258) served by ``ServingEngine`` in bfloat16; and its plain
reference.

The model (0-indexed layer ``l`` of 32, ``M`` = 2560), every layer
``x = x + Mix_l(LN(x))`` then ``x = x + MLP(LN(x))``, LN with gamma and beta,
``MLP(h) = W2 (silu(g) * u)``, ``[g, u] = W1 h``; a final LN; logits
``x E^T`` with the tied embedding; no position anywhere. ``Mix_l``:

    l even, l <= 16   Mamba-1: [x, z] = W_in h; x = silu(conv1d_4(x) + b);
                      [dt, B, C] = W_x x; dt = softplus(W_dt dt + b_dt);
                      s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t;
                      y_t = C_t . s_t + D x_t;  Mix = W_out (y * silu(z)).
                      Layer 16 also hands m_t = y_t to the layers below.
    l odd, l < 16     differential attention over the last 512 keys
    l = 17            the same over every key: the one full-length cache
    l even, l >= 18   gated memory unit: Mix = W_out (silu(W_in h) * m_t)
    l odd, l >= 19    q only; K and V are layer 17's; the same differential
                      form with the layer's own lambda vectors and RMSNorm

Differential attention: 40 query heads of 64 pair into 20, 20 K/V heads into
10, query pair p reads K/V pair p // 2; with a_s = softmax(q_s k_s^T / 8):
``o = (a_1 - lambda a_2) [v1, v2]``, ``o = RMSNorm_128(o) (1 - lambda_init)``,
``lambda = exp(lq1.lk1) - exp(lq2.lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 l)``.

The reference computes exactly that in float32 on the served weights cast
up, a layer at a time — plain ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: a sequential ``lax.scan`` over
time for the state-space layers, dense masked attention (one K/V pair's four
query heads at a time), the head in blocks of the vocabulary, no kernel, no
cache, no batching, and nothing imported from ``ops/`` or ``serving/``
(``serving_config`` and ``init_params`` are the driver's, not the
reference's). Every sequence is padded to ``reference.seq_pad`` so that ONE
compiled program scores every request.
"""
import functools

# Two bands, this configuration's own, both set from the chip at the published
# widths (PERF.md sections 4 and 6, PR 31; the readings are in the JSON's
# ``bands`` object) and both over the weights the driver drew.
#
# PROBE_RTOL bounds the dense comparison of ``make_probe``: a row's error is
# its largest served-minus-reference logit in units of the row's largest
# reference logit. Half of the ``reference.probe_rows`` rows are prefills of
# a prefix of the text, half a prefill followed by 64-256 forced decode
# steps that start before and end after position 512 — all three kinds of
# per-stream state. Each half has its first quartile, and the LARGER of the
# two is what the band bounds, so that a fault of the decode path alone
# cannot hide behind sound prefills. Sound bfloat16 serving reads 4.9-5.5%
# there over sixteen runs (32 layers of bf16 activations and a 200k-row
# head; an 8-layer model of the same kinds reads 1.8%); the memory zeroed
# 14.2%, every weight rounded to float8 54%, lambda = 0 70%, half the window
# 98%; the SSM states never updated during decode 26%, the conv tails never
# shifted 134% (their prefilled halves 5.0%).
#
# LOGIT_RTOL is the "same token" band of ``make_reference``: a served token
# counts as the reference's when its reference logit is within LOGIT_RTOL of
# the position's largest, in units of that largest's magnitude. Sound
# serving moves a row's logits by 5-7% of its largest, so its token may be
# the reference's second choice by up to twice that: its worst gaps read
# 4.0-5.0% over three seeds of 1,792 tokens and once over 5% in fourteen
# runs' re-scored requests; float8 weights read far above (``bands``).
LOGIT_RTOL = 1e-1
PROBE_RTOL = 8e-2


def serving_config(cfg):
    """The ``ServingConfig`` of this configuration file: its ``model`` and
    ``engine`` objects, as ``tools/serve.py --model-config`` reads them."""
    from mxnet_tpu.serving import ServingConfig

    return ServingConfig.from_json(cfg)


def init_params(cfg, seed):
    """The weights, made ON the device from the seed in the type they are
    served in: N(0, std) (times the JSON's ``init`` gains by name), biases
    0, LN 1/0; Mamba's published init (``A_log = log(1..N)``, ``D = 1``,
    ``b_dt`` the inverse softplus of log-uniform [1e-3, 1e-1]); the lambda
    vectors N(0, 0.1). One small program per distinct shape."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import model as lm

    shapes = lm.param_shapes(serving_config(cfg))
    dtype = jnp.dtype(cfg["weights_dtype"])
    init = cfg.get("init", {})
    std = init.get("std", 0.02)
    gains = init.get("gains", {})

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    @functools.partial(jax.jit, static_argnums=(1,))
    def dt_bias(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    key = jax.random.PRNGKey(int(seed))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape, k = shapes[name], jax.random.fold_in(key, i)
        if name.endswith(("_gamma", "_ssm_d")):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith("_ssm_dt_bias"):
            out[name] = dt_bias(k, shape)
        elif name.endswith("_ssm_a_log"):
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32))[:, None],
                shape).astype(dtype)
        elif name.endswith(("_beta", "_bias")):
            out[name] = jnp.zeros(shape, dtype)
        elif "_diff_lambda_" in name:
            out[name] = draw(k, shape, 0.1)
        else:
            gain = next((g for part, g in gains.items() if part in name), 1.0)
            out[name] = draw(k, shape, float(std * gain))
    return out


# ------------------------------------------------------------ reference --
def _ln(t, gamma, beta):
    import jax.numpy as jnp

    mean = jnp.mean(t, -1, keepdims=True)
    var = jnp.mean((t - mean) ** 2, -1, keepdims=True)
    return (t - mean) / jnp.sqrt(var + 1e-5) * gamma + beta


def _mamba(h, w, m):
    """One Mamba-1 layer over h (S, M): ``(y (S, Dn), Mix (S, M))``; ``w``
    gives the layer's weights in float32 by their short names."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    seq = h.shape[0]
    taps, n, rank = m["ssm_conv"], m["ssm_state"], m["ssm_dt_rank"]
    x, z = jnp.split(jnp.dot(h, w("ssm_in_weight").T, precision=hi), 2, -1)
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    cw = w("ssm_conv_weight")                       # (taps, Dn), oldest first
    x = jax.nn.silu(sum(xp[k:k + seq] * cw[k] for k in range(taps))
                    + w("ssm_conv_bias"))
    dbc = jnp.dot(x, w("ssm_x_weight").T, precision=hi)
    dt = jax.nn.softplus(jnp.dot(dbc[:, :rank], w("ssm_dt_weight").T,
                                 precision=hi) + w("ssm_dt_bias"))
    b, c = dbc[:, rank:rank + n], dbc[:, rank + n:]
    a = -jnp.exp(w("ssm_a_log"))                    # (N, Dn)
    d = w("ssm_d")

    def step(s, xs):
        xt, dtt, bt, ct = xs
        s = jnp.exp(dtt[None] * a) * s + (dtt * xt)[None] * bt[:, None]
        return s, jnp.sum(ct[:, None] * s, 0) + d * xt

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (x, dt, b, c))
    return y, jnp.dot(y * jax.nn.silu(z), w("ssm_out_weight").T,
                      precision=hi)


def _diff_attention(q, k, v, w, layer, window, m):
    """Differential attention, q (S, Hq hd), k and v (S, Hkv hd), dense and
    masked: key j is visible to query i iff ``i - window < j <= i`` (every
    ``j <= i`` with no window). One K/V pair and its query pairs at a time."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    seq = q.shape[0]
    hd, pairs, kv_pairs = (m["head_dim"], m["num_heads"] // 2,
                           m["num_kv_heads"] // 2)
    group = pairs // kv_pairs
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(w("diff_lambda_q1") * w("diff_lambda_k1")))
           - jnp.exp(jnp.sum(w("diff_lambda_q2") * w("diff_lambda_k2")))
           + lam_init)
    # (kv pair, query pair of it, head of the pair, S, hd)
    qs = q.reshape(seq, kv_pairs, group, 2, hd).transpose(1, 2, 3, 0, 4)
    ks = k.reshape(seq, kv_pairs, 2, hd).transpose(1, 2, 0, 3)
    vs = v.reshape(seq, kv_pairs, 2 * hd).transpose(1, 0, 2)

    def one(xs):
        qc, kc, vc = xs                 # (group, 2, S, hd) (2, S, hd) (S, 2hd)
        s = jnp.einsum("gsqd,skd->gsqk", qc, kc, precision=hi) / jnp.sqrt(
            jnp.float32(hd))
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("gqk,kd->gqd", a[:, 0] - lam * a[:, 1], vc,
                          precision=hi)                  # (group, S, 2hd)

    o = jax.lax.map(one, (qs, ks, vs))                   # (kvp, group, S, 2hd)
    o = o.transpose(2, 0, 1, 3)                          # (S, kvp, group, 2hd)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) \
        * w("diff_norm_gamma") * (1.0 - lam_init)
    return o.reshape(seq, pairs * 2 * hd)


def _hidden(params, tokens, m):
    """The final-normed hidden state (S, M) of ``tokens`` (S,), fp32."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    hq, hkv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    x = params["embed_weight"][tokens].astype(f32)
    memory = kv = None
    for l, kind in enumerate(m["layer_kinds"]):
        def w(name, _p="layer%d_" % l):
            return params[_p + name].astype(f32)

        h = _ln(x, w("ln1_gamma")[0, 0], w("ln1_beta")[0, 0])
        if kind == "mamba":
            y, mix = _mamba(h, w, m)
            memory = y              # the last one before the GMUs is read
        elif kind == "gmu":
            g = jax.nn.silu(jnp.dot(h, w("gmu_in_weight").T, precision=hi))
            mix = jnp.dot(g * memory, w("gmu_out_weight").T, precision=hi)
        else:
            if kind == "cross":
                q = jnp.dot(h, w("attn_q_weight").T, precision=hi) \
                    + w("attn_q_bias")
                k, v = kv
            else:
                qkv = jnp.dot(h, w("attn_in_weight").T, precision=hi) \
                    + w("attn_in_bias")
                q, k, v = (qkv[:, :hq], qkv[:, hq:hq + hkv],
                           qkv[:, hq + hkv:])
                if kind == "full":
                    kv = (k, v)
            o = _diff_attention(q, k, v, w, l,
                                m["window"] if kind == "swa" else 0, m)
            mix = jnp.dot(o, w("attn_out_weight").T, precision=hi) \
                + w("attn_out_bias")
        x = x + mix
        h = _ln(x, w("ln2_gamma")[0, 0], w("ln2_beta")[0, 0])
        gate, up = jnp.split(jnp.dot(h, w("ffn1_weight").T, precision=hi),
                             2, -1)
        x = x + jnp.dot(jax.nn.silu(gate) * up, w("ffn2_weight").T,
                        precision=hi)
    return _ln(x, params["final_ln_gamma"].astype(f32)[0, 0],
               params["final_ln_beta"].astype(f32)[0, 0])


def _vocab_blocks(embed):
    """The tied embedding (V, M) as (n, V / n, M): the head is taken a block
    of the vocabulary at a time (a float32 copy of it all is 2 GB)."""
    v = embed.shape[0]
    n = next(d for d in range(min(64, v), 0, -1) if v % d == 0)
    return embed.reshape(n, v // n, embed.shape[1])


def _head(x, embed):
    """x (R, M) -> logits (R, V), float32."""
    import jax
    import jax.numpy as jnp

    blocks = _vocab_blocks(embed)
    out = jax.lax.map(
        lambda e: jnp.dot(x, e.astype(jnp.float32).T,
                          precision=jax.lax.Precision.HIGHEST), blocks)
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)


def _logits(params, tokens, m):
    return _head(_hidden(params, tokens, m), params["embed_weight"])


def _score(params, tokens, n_prompt, generated, m):
    """For each generated token j: the reference's logit of that token, the
    largest logit of its position, and the reference's own argmax, a block
    of the vocabulary at a time. ``tokens`` is prompt + generated[:-1],
    zero-padded."""
    import jax
    import jax.numpy as jnp

    x = _hidden(params, tokens, m)
    rows = jnp.clip(n_prompt - 1 + jnp.arange(generated.shape[0]), 0,
                    tokens.shape[0] - 1)
    x = jnp.take(x, rows, axis=0)
    blocks = _vocab_blocks(params["embed_weight"])
    width = blocks.shape[1]

    def one(xs):
        e, n = xs
        lg = jnp.dot(x, e.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)        # (R, width)
        local = generated - n * width
        mine = (local >= 0) & (local < width)
        chosen = jnp.take_along_axis(
            lg, jnp.clip(local, 0, width - 1)[:, None], axis=1)[:, 0]
        return (jnp.where(mine, chosen, -jnp.inf), lg.max(-1),
                lg.argmax(-1) + n * width)

    chosen, top, arg = jax.lax.map(one, (blocks, jnp.arange(blocks.shape[0])))
    best = top.argmax(0)
    return (chosen.max(0), top.max(0),
            jnp.take_along_axis(arg, best[None], axis=0)[0])


def _rows_logits(params, tokens, rows, m):
    """The reference's logits (K, V) at positions ``rows`` of ``tokens``."""
    import jax.numpy as jnp

    return _head(jnp.take(_hidden(params, tokens, m), rows, axis=0),
                 params["embed_weight"])


def probe_plan(cfg, seed):
    """The probe's rows, from the seed: ``(n, decode_from)`` pairs over a
    text of ``reference.probe_len`` tokens. Half are prefills of the first
    ``n`` tokens (``decode_from`` None; n spread up to the text's length),
    half a prefill of ``decode_from`` tokens followed by ``n - decode_from``
    forced decode steps, 64 to 256 of them, that start before position
    ``model.window`` and end after it."""
    import numpy as np

    ref = cfg["reference"]
    length, k, window = ref["probe_len"], ref["probe_rows"], \
        cfg["model"]["window"]
    rng = np.random.RandomState((seed + 1) % 2 ** 32)
    lo, hi = ref.get("probe_decode", [64, 256])
    lo, hi = min(lo, window - 1), min(hi, window - 1, length - window - 1)
    plan = [(int(n), None) for n in np.unique(
        np.linspace(length // (k // 2), length, k // 2).astype(np.int32))]
    for _ in range(k - len(plan)):
        steps = int(rng.randint(lo, hi + 1))
        start = int(rng.randint(max(1, window - steps + 1), window))
        plan.append((start + steps, start))     # start < window < start+steps
    return plan


def make_probe(cfg):
    """``probe(params, logits_of, seed) -> {"quartile", "median", "worst",
    "rows", ...}``: the served next-token logits, ``logits_of(tokens,
    decode_from=None) -> (V,)`` (the engine's ``prefill_logits``), against
    the reference's over ``params`` at the rows of :func:`probe_plan` of
    one seeded random text. A row's error is its largest difference in
    units of the row's largest reference logit. ``prefill_quartile`` and
    ``decode_quartile`` are the first quartiles of the two halves, and
    ``quartile``, which ``PROBE_RTOL`` bounds, is the LARGER of them: a
    fault of the decode path alone (a state not carried, a conv tail not
    shifted, a window block freed too early) moves only the decoded rows,
    and a quartile over both halves together would still be drawn from the
    sound prefills."""
    import jax
    import numpy as np

    length = cfg["reference"]["probe_len"]
    fn = jax.jit(functools.partial(_rows_logits, m=cfg["model"]))

    def probe(params, logits_of, seed):
        plan = probe_plan(cfg, seed)
        text = np.random.RandomState(seed % 2 ** 32).randint(
            0, cfg["model"]["vocab"], length).astype(np.int32)
        ends = np.asarray([n for n, _ in plan], np.int32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(fn(params, text, ends - 1))
        errors = []
        for (n, start), w in zip(plan, want):
            got = logits_of(text[:n]) if start is None \
                else logits_of(text[:n], decode_from=start)
            errors.append(float(np.abs(got - w).max() / np.abs(w).max()))
        errors = np.asarray(errors)
        decoded = np.asarray([s is not None for _, s in plan])

        def q1(e):
            return float(np.percentile(e, 25)) if len(e) else None

        halves = q1(errors[~decoded]), q1(errors[decoded])
        return {"quartile": max(q for q in halves if q is not None),
                "median": float(np.median(errors)),
                "worst": float(errors.max()), "rows": len(errors),
                "prefill_quartile": halves[0], "decode_quartile": halves[1]}

    return probe


def reference_logits(cfg):
    """``logits(params, tokens) -> (S, V)`` float32: the reference's full
    forward over one unpadded sequence (the tests and the chip check
    compare the engine's logits with it)."""
    import jax
    import numpy as np

    fn = jax.jit(functools.partial(_logits, m=cfg["model"]))

    def logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return np.asarray(fn(params, np.asarray(tokens, np.int32)))

    return logits


def make_reference(cfg):
    """``score(params, prompt, generated) -> (off, argmax_matches)``:
    positions whose served token is outside the band, and how many served
    tokens are the reference's exact argmax. ``score.gaps`` gives the
    distances themselves, (largest - chosen) / |largest| per position."""
    import jax
    import numpy as np

    seq_pad, gen_max = (cfg["reference"]["seq_pad"],
                        cfg["reference"]["gen_max"])
    fn = jax.jit(functools.partial(_score, m=cfg["model"]))

    def run(params, prompt, generated):
        n = len(generated)
        if n > gen_max or len(prompt) + n > seq_pad:
            raise ValueError("request too long for the reference program "
                             "(prompt %d + %d generated > %d)"
                             % (len(prompt), n, seq_pad))
        toks = np.zeros(seq_pad, np.int32)
        seq = list(prompt) + list(generated[:-1])
        toks[:len(seq)] = seq
        gen = np.zeros(gen_max, np.int32)
        gen[:n] = generated
        with jax.default_matmul_precision("highest"):
            chosen, top, arg = (np.asarray(a)[:n] for a in fn(
                params, toks, np.int32(len(prompt)), gen))
        return chosen.astype(np.float64), top.astype(np.float64), arg

    def gaps(params, prompt, generated):
        chosen, top, _arg = run(params, prompt, generated)
        return (top - chosen) / np.abs(top)

    def score(params, prompt, generated):
        chosen, top, arg = run(params, prompt, generated)
        off = [j for j in range(len(generated))
               if top[j] - chosen[j] > LOGIT_RTOL * abs(top[j])]
        return off, int((arg == np.asarray(generated)).sum())

    score.gaps = gaps
    return score
