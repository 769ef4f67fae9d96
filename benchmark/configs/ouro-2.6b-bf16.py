"""Ouro-2.6B (ByteDance/Ouro-2.6B; the LoopLM paper, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741) served by
``ServingEngine`` in bfloat16; and its plain reference.

A stack of 48 layers that every token goes through ``total_ut_steps`` = 4
times with the SAME weights, each pass with a K/V cache of its own. With
``h`` a token's row of 2,048, ``RMS_g(x) = g * x / sqrt(mean(x^2) + 1e-6)``,
layer ``i``'s four gammas ``a_i, b_i, c_i, d_i`` and no bias anywhere:

    x = E[token]                                     no scaling
    for r in 0..3:
        for i in 0..47:
            u = RMS_{a_i}(x)
            q, k, v = W_q u, W_k u, W_v u            16 heads of 128
            q, k = rope(q), rope(k)                  rotate-half, theta 1e6
            s = softmax(q k^T / sqrt(128)) v         causal, over THIS pass's
                                                     keys and values only
            x = x + RMS_{b_i}(W_o s)
            u = RMS_{c_i}(x)
            x = x + RMS_{d_i}(W_d (silu(W_g u) * (W_u u)))
        x = RMS_{g_final}(x)                         closes EVERY pass; the
        lambda_r = sigmoid(w_exit . x + b_exit)      next pass's input
    logits = W_head x                                from the last pass
    p_r = lambda_r * prod_{j<r} (1 - lambda_j)       the last pass takes the
                                                     remainder

At the published ``early_exit_threshold`` 1.0 no token leaves before the
last pass: the served logits are pass 3's and do not depend on the gate.

The reference computes exactly that in float32 on the served weights cast
up — plain ``jax.numpy``, every matmul at ``Precision.HIGHEST`` under
``jax.default_matmul_precision("highest")``, a dense causal mask, NO cache
(all positions at once, pass after pass: a pass's keys are that pass's
projections, which is what "a cache of its own" means without a cache), one
weight matrix upcast at a time, and nothing imported from ``mxnet_tpu/ops/``
or ``mxnet_tpu/serving/``. The passes are a ``lax.fori_loop`` so that the
program is one pass's 48 bodies and compiles in a pass's time; every
sequence is padded to ``reference.seq_pad`` so that ONE program scores
every request.
"""
import functools

# Two bands, both over the weights the driver drew and both set from the chip
# at the published widths (PERF.md sections 4 and 6, PR 40), with the
# post-norms' gammas drawn at ``init.post_norm_gamma`` 0.25 (at 1 the random
# looped stack amplifies a rounding about twofold a pass and SOUND bfloat16
# serving reads 23-29% here: the configuration file's ``departures``).
#
# PROBE_RTOL bounds the dense comparison of ``make_probe``: a row's largest
# served-minus-reference logit in units of the row's largest reference
# logit; half the rows are prefills of a prefix (32..384 tokens), half a
# prefill followed by 16..48 forced decode steps through the 192-layer
# cache; each half has its first quartile and the LARGER is held under the
# band. Sound bfloat16 serving — bf16 weights, pages and residual stream,
# fp32 accumulation and norm statistics — reads 3.12-3.89% over eighteen
# seeds; every K and V rounded to float8 (the nearest precision below)
# 13.3-17.2% over four; three passes for four 54%; every pass on pass 0's
# cache 76% on the decoded half (3.4 on the prefilled: prefill attends over
# its own projections); the post-norms left out 125%. The band is 1.8
# times sound's largest and the nearest fault 1.9 times the band.
#
# LOGIT_RTOL is the "same token" band of ``make_reference``: a served token
# counts as the reference's when its reference logit is within LOGIT_RTOL
# of the position's largest, in units of that largest's magnitude. Sound
# serving's worst gap over six generated requests of 200 and 256 tokens is
# 1.5-2.7%; with K and V in float8 8.8 and 11.0% (46 tokens of 456 over
# 5%). The band is 2.2 times sound's worst; the dense probe is the band
# that sees a wrong layer.
LOGIT_RTOL = 6e-2
PROBE_RTOL = 7e-2
INIT_SCALE = 0.02       # serving/model.py random_params' scale


def serving_config(cfg):
    """The ``ServingConfig`` of this configuration file: its ``model`` and
    ``engine`` objects, as ``tools/serve.py --model-config`` reads them. A
    program from before the looped stack stops here, at once."""
    from mxnet_tpu.serving import ServingConfig

    try:
        return ServingConfig.from_json(cfg)
    except TypeError as e:
        raise SystemExit(
            "this program cannot serve %s: its ModelConfig knows no stack "
            "that runs several times (%s)" % (cfg["name"], e))


def init_params(cfg, seed):
    """The weights, made ON the device from the seed in the type they are
    served in (N(0, 0.02), the gate's bias 0, gammas 1 but for the two
    norms on a layer's sub-layer OUTPUTS, which are ``init.post_norm_gamma``:
    the configuration file's ``departures`` say why): one small program per
    distinct shape, so that no more than one array's float32 draw is alive
    at a time."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import model as lm

    shapes = lm.param_shapes(serving_config(cfg))
    dtype = jnp.dtype(cfg["weights_dtype"])
    init = cfg.get("init", {})
    std = init.get("std", INIT_SCALE)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    key = jax.random.PRNGKey(int(seed))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        if name.endswith("_post_gamma"):
            out[name] = jnp.full(shapes[name],
                                 init.get("post_norm_gamma", 1.0), dtype)
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shapes[name], dtype)
        elif name.endswith("_bias"):
            out[name] = jnp.zeros(shapes[name], dtype)
        else:
            out[name] = draw(jax.random.fold_in(key, i), shapes[name],
                             float(std))
    return out


# ------------------------------------------------------------ reference --
def _rms(t, gamma, eps):
    import jax.numpy as jnp

    return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps) * gamma


def _rope(t, theta):
    """(S, H, hd) at positions 0..S-1: t * cos + rotate_half(t) * sin."""
    import jax.numpy as jnp

    seq, _, hd = t.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None]         # (S, 1, hd)
    half = jnp.concatenate([-t[..., hd // 2:], t[..., :hd // 2]], -1)
    return t * jnp.cos(emb) + half * jnp.sin(emb)


def _attention(q, k, v):
    """Plain causal attention, (S, H, hd) each, fp32."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    seq, _, hd = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=hi) / jnp.sqrt(
        jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=hi)


def _stack(params, x, m):
    """One pass: the layers in turn and the final norm, (S, M) fp32."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    heads, hd, eps = m["num_heads"], m["head_dim"], m["norm_eps"]
    seq = x.shape[0]

    def w(name):
        return params[name].astype(jnp.float32)

    def gamma(name):
        return w(name)[0, 0]

    for i in range(m["num_layers"]):
        p = "layer%d" % i
        u = _rms(x, gamma(p + "_ln1_gamma"), eps)
        q, k, v = (t.reshape(seq, heads, hd) for t in jnp.split(
            jnp.dot(u, w(p + "_attn_in_weight").T, precision=hi), 3, -1))
        att = _attention(_rope(q, m["rope_theta"]), _rope(k, m["rope_theta"]),
                         v).reshape(seq, heads * hd)
        out = jnp.dot(att, w(p + "_attn_out_weight").T, precision=hi)
        if m.get("post_norm"):
            out = _rms(out, gamma(p + "_ln1_post_gamma"), eps)
        x = x + out
        u = _rms(x, gamma(p + "_ln2_gamma"), eps)
        g, up = jnp.split(jnp.dot(u, w(p + "_ffn1_weight").T, precision=hi),
                          2, -1)
        out = jnp.dot(jax.nn.silu(g) * up, w(p + "_ffn2_weight").T,
                      precision=hi)
        if m.get("post_norm"):
            out = _rms(out, gamma(p + "_ln2_post_gamma"), eps)
        x = x + out
    return _rms(x, gamma("final_ln_gamma"), eps)


def _hidden(params, tokens, m):
    """``(x, p)``: the last pass's final-normed hidden state (S, M) of
    ``tokens`` (S,), and the exit distribution (S, R) over the passes."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    passes = m.get("loop_steps", 1)
    x = params["embed_weight"][tokens].astype(jnp.float32)
    if passes == 1:
        return _stack(params, x, m), jnp.ones((tokens.shape[0], 1))

    def one(r, carry):
        x, lam = carry
        x = _stack(params, x, m)
        gate = jax.nn.sigmoid(
            jnp.dot(x, params["early_exit_gate_weight"].astype(
                jnp.float32)[0], precision=hi)
            + params["early_exit_gate_bias"].astype(jnp.float32)[0])
        return x, lam.at[r].set(gate)

    x, lam = jax.lax.fori_loop(
        0, passes, one, (x, jnp.zeros((passes, tokens.shape[0]))))
    # p_r = lambda_r prod_{j<r} (1 - lambda_j); the last takes what is left
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]], 0)
    return x, p.T


def _head(params, x):
    import jax
    import jax.numpy as jnp

    return jnp.dot(x, params["lm_head_weight"].astype(jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)


def _logits(params, tokens, m):
    x, p = _hidden(params, tokens, m)
    return _head(params, x), p


def _score(params, tokens, n_prompt, generated, m):
    """For each generated token j: the reference's logit of that token, the
    largest logit of its position, and the reference's own argmax.
    ``tokens`` is prompt + generated[:-1], zero-padded."""
    import jax.numpy as jnp

    x, _p = _hidden(params, tokens, m)
    # position n_prompt-1+j of prompt+generated[:-1] scores token j
    rows = jnp.clip(n_prompt - 1 + jnp.arange(generated.shape[0]), 0,
                    tokens.shape[0] - 1)
    logits = _head(params, jnp.take(x, rows, axis=0))
    chosen = jnp.take_along_axis(logits, generated[:, None], axis=1)[:, 0]
    return chosen, logits.max(-1), logits.argmax(-1)


def _rows_logits(params, tokens, rows, m):
    """The reference's logits (K, V) at positions ``rows`` of ``tokens``."""
    import jax.numpy as jnp

    return _head(params, jnp.take(_hidden(params, tokens, m)[0], rows,
                                  axis=0))


def probe_plan(cfg, seed):
    """The probe's rows, from the seed: ``(n, decode_from)`` pairs over a
    text of ``reference.probe_len`` tokens. Half are prefills of the first
    ``n`` tokens (``decode_from`` None), n spread over the mix's prompt
    lengths ``reference.probe_prefix``; half a prefill of ``decode_from``
    tokens (drawn from the same range) followed by ``n - decode_from``
    forced decode steps, ``reference.probe_decode`` of them, through every
    pass's cache."""
    import numpy as np

    ref = cfg["reference"]
    k = ref["probe_rows"]
    (p_lo, p_hi), (d_lo, d_hi) = ref["probe_prefix"], ref["probe_decode"]
    rng = np.random.RandomState((seed + 1) % 2 ** 32)
    plan = [(int(n), None) for n in np.unique(
        np.linspace(p_lo, p_hi, k // 2).astype(np.int32))]
    for start in np.linspace(p_lo, p_hi, k - len(plan)).astype(np.int32):
        plan.append((int(start) + int(rng.randint(d_lo, d_hi + 1)),
                     int(start)))
    return plan


def make_probe(cfg):
    """``probe(params, logits_of, seed) -> {"quartile", "median", "worst",
    "rows", "prefill_quartile", "decode_quartile"}``: the served next-token
    logits, ``logits_of(tokens, decode_from=None) -> (V,)`` (the engine's
    ``prefill_logits``), against the reference's over ``params`` at the
    rows of :func:`probe_plan` of one seeded random text. A row's error is
    its largest difference in units of the row's largest reference logit.
    ``quartile``, which ``PROBE_RTOL`` bounds, is the LARGER of the two
    halves' first quartiles: a fault of the decode path alone (a pass
    reading another pass's cache) moves only the decoded rows."""
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
        halves = [float(np.percentile(errors[pick], 25))
                  for pick in (~decoded, decoded)]
        return {"quartile": max(halves), "median": float(np.median(errors)),
                "worst": float(errors.max()), "rows": len(errors),
                "prefill_quartile": halves[0], "decode_quartile": halves[1]}

    return probe


def reference_logits(cfg):
    """``logits(params, tokens, exits=False) -> (S, V)`` float32: the
    reference's full forward over one unpadded sequence (the tests and the
    chip check compare the engine's logits with it); with ``exits`` also
    the exit distribution ``p`` (S, R) over the passes, rows summing to 1."""
    import jax
    import numpy as np

    fn = jax.jit(functools.partial(_logits, m=cfg["model"]))

    def logits(params, tokens, exits=False):
        with jax.default_matmul_precision("highest"):
            out, p = (np.asarray(a) for a in fn(
                params, np.asarray(tokens, np.int32)))
        return (out, p) if exits else out

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
