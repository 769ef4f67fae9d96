"""OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct, arXiv:2409.02060) served
by ``ServingEngine`` in bfloat16; and its plain reference.

The block, as ``modeling_olmoe.py`` has it (x one token's hidden vector,
no bias anywhere):

    n1  = RMSNorm_1(x)          RMSNorm(t) = t / sqrt(mean(t^2) + 1e-5) * gamma
    q   = RoPE(RMSNorm_q(W_q n1))   k = RoPE(RMSNorm_k(W_k n1))   v = W_v n1
          (QK-norm over the whole 2048-wide projection, before the split
          into 16 heads of 128; RoPE theta 10000, rotate-half, absolute
          position)
    a   = x + W_o Attn(q, k, v)     causal softmax(q.k / sqrt(128)) v per head
    n2  = RMSNorm_2(a)
    p   = softmax(W_r n2) over all 64 experts; the 8 largest, NOT renormalised
    out = a + sum_{e in top8} p_e W_down_e (silu(W_gate_e n2) * (W_up_e n2))
    logits = W_head RMSNorm_f(x_last)

The reference computes exactly that in float32 on the served weights cast
up — plain ``jax.numpy``, every matmul at ``Precision.HIGHEST`` under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching, and nothing imported from ``ops/moe.py`` or ``serving/model.py``.
The experts are a loop over all of them with a mask: every token goes
through every expert, one expert's weights in float32 at a time, and only
its chosen eight are added. Every sequence is padded to
``reference.seq_pad`` so that ONE compiled program scores every request
(under the causal mask the padded tail cannot reach an earlier position).
"""
import functools

# Two bands, this configuration's own, both set from the chip at the
# published widths (PERF.md sections 4 and 6, PR 26) and both over the
# weights the driver drew.
#
# PROBE_RTOL bounds the dense comparison of ``make_probe``: the first
# quartile, over 64 prefixes of a text, of a row's largest served-minus-
# reference logit in units of the row's largest reference logit. Sound
# bfloat16 serving — bf16 weights, pages and residual stream, fp32
# accumulation, norm statistics and router softmax — reads 0.65-1.02% there
# over thirteen seeds; the experts' weights rounded to float8 1.76-1.96%,
# seven experts for eight 4.2-4.6%, every weight in float8 12.9%. This is
# the band that sees the expert layer.
#
# LOGIT_RTOL is the "same token" band of ``make_reference``: with random
# weights the largest logits of a position lie close together, so a served
# token counts as the reference's when its reference logit is within
# LOGIT_RTOL of the position's largest, in units of that largest's
# magnitude. A served token tells only at the one position in thirty whose
# best two tokens lie close, and there sound serving's own worst gap
# (1.8%: a rounding flipped a near-tied expert choice) is of the size of a
# dropped expert's (3.9%) or of float8 experts' (2.5%): the band is three
# times the sound worst and guards against gross faults only (every
# weight in float8 reads 8.6% and fails it).
LOGIT_RTOL = 5e-2
PROBE_RTOL = 1.4e-2
INIT_SCALE = 0.02       # serving/model.py random_params' scale


def serving_config(cfg):
    """The ``ServingConfig`` of this configuration file: its ``model`` and
    ``engine`` objects, as ``tools/serve.py --model-config`` reads them."""
    from mxnet_tpu.serving import ServingConfig

    return ServingConfig.from_json(cfg)


def init_params(cfg, seed):
    """The weights, made ON the device from the seed in the type they are
    served in (N(0, 0.02), gammas 1): one small program per distinct shape,
    so that no more than one array's float32 draw is alive at a time."""
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
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shapes[name], dtype)
            continue
        gain = init.get("expert_gain", 1.0) if "_experts_" in name else 1.0
        out[name] = draw(jax.random.fold_in(key, i), shapes[name],
                         float(std * gain))
    return out


# ------------------------------------------------------------ reference --
def _rms(t, gamma):
    import jax.numpy as jnp

    return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-5) * gamma


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


def _experts(n2, router, gate, up, down, k):
    """Every token through every expert, one expert at a time; a token's
    chosen k are added with their softmax weights, the others with 0."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    probs = jax.nn.softmax(jnp.dot(n2, router.astype(f32).T, precision=hi))
    top_w, top_i = jax.lax.top_k(probs, k)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(n2.shape[0])[:, None], top_i].set(top_w)     # (S, E)

    def one(acc, xs):
        wg, wu, wd, w = xs
        h = jax.nn.silu(jnp.dot(n2, wg.astype(f32).T, precision=hi)) \
            * jnp.dot(n2, wu.astype(f32).T, precision=hi)
        return acc + w[:, None] * jnp.dot(h, wd.astype(f32).T,
                                          precision=hi), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n2),
                          (gate, up, down, weight.T))
    return out


def _hidden(params, tokens, m):
    """The final-normed hidden state (S, M) of ``tokens`` (S,), fp32."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    heads, hd = m["num_heads"], m["head_dim"]
    seq = tokens.shape[0]

    def w(name):
        return params[name].astype(jnp.float32)

    x = params["embed_weight"][tokens].astype(jnp.float32)
    for i in range(m["num_layers"]):
        p = "layer%d" % i
        n1 = _rms(x, w(p + "_ln1_gamma")[0, 0])
        qkv = jnp.dot(n1, w(p + "_attn_in_weight").T, precision=hi)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = _rms(q, w(p + "_q_norm_gamma")).reshape(seq, heads, hd)
        k = _rms(k, w(p + "_k_norm_gamma")).reshape(seq, heads, hd)
        att = _attention(_rope(q, m["rope_theta"]), _rope(k, m["rope_theta"]),
                         v.reshape(seq, heads, hd))
        x = x + jnp.dot(att.reshape(seq, heads * hd),
                        w(p + "_attn_out_weight").T, precision=hi)
        n2 = _rms(x, w(p + "_ln2_gamma")[0, 0])
        x = x + _experts(n2, params[p + "_router_weight"],
                         params[p + "_experts_gate_weight"],
                         params[p + "_experts_up_weight"],
                         params[p + "_experts_down_weight"],
                         m["experts_per_tok"])
    return _rms(x, w("final_ln_gamma")[0, 0])


def _logits(params, tokens, m):
    import jax
    import jax.numpy as jnp

    return jnp.dot(_hidden(params, tokens, m),
                   params["lm_head_weight"].astype(jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)


def _score(params, tokens, n_prompt, generated, m):
    """For each generated token j: the reference's logit of that token, the
    largest logit of its position, and the reference's own argmax.
    ``tokens`` is prompt + generated[:-1], zero-padded."""
    import jax
    import jax.numpy as jnp

    x = _hidden(params, tokens, m)
    # position n_prompt-1+j of prompt+generated[:-1] scores token j
    rows = jnp.clip(n_prompt - 1 + jnp.arange(generated.shape[0]), 0,
                    tokens.shape[0] - 1)
    logits = jnp.dot(jnp.take(x, rows, axis=0),
                     params["lm_head_weight"].astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    chosen = jnp.take_along_axis(logits, generated[:, None], axis=1)[:, 0]
    return chosen, logits.max(-1), logits.argmax(-1)


def _rows_logits(params, tokens, rows, m):
    """The reference's logits (K, V) at positions ``rows`` of ``tokens``."""
    import jax
    import jax.numpy as jnp

    x = jnp.take(_hidden(params, tokens, m), rows, axis=0)
    return jnp.dot(x, params["lm_head_weight"].astype(jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)


def make_probe(cfg):
    """``probe(params, logits_of, seed) -> {"quartile", "median", "worst",
    "rows"}``: the served next-token logits, ``logits_of(tokens) -> (V,)``
    (the engine's ``prefill_logits``), against the reference's over
    ``params`` at ``reference.probe_rows`` prefixes of one seeded random
    text of ``reference.probe_len`` tokens. A row's error is its largest
    difference in units of the row's largest reference logit.
    ``quartile`` is the first quartile over the rows and is what
    ``PROBE_RTOL`` bounds: a wrong layer moves every row, while the rows
    in which a rounding flipped a near-tied expert choice (a third to two
    thirds of them on the chip) lie above it and move ``median`` and
    ``worst`` about."""
    import jax
    import numpy as np

    length, k = cfg["reference"]["probe_len"], cfg["reference"]["probe_rows"]
    ends = np.unique(np.linspace(length // k, length, k).astype(np.int32))
    fn = jax.jit(functools.partial(_rows_logits, m=cfg["model"]))

    def probe(params, logits_of, seed):
        text = np.random.RandomState(seed % 2 ** 32).randint(
            0, cfg["model"]["vocab"], length).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(fn(params, text, ends - 1))
        errors = [float(np.abs(logits_of(text[:n]) - w).max()
                        / np.abs(w).max()) for n, w in zip(ends, want)]
        return {"quartile": float(np.percentile(errors, 25)),
                "median": float(np.median(errors)),
                "worst": max(errors), "rows": len(errors)}

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
