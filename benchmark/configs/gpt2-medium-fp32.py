"""GPT-2 medium at its published sizes, served by ``ServingEngine``; and its
plain reference.

The reference is a copy of ``chip_smoke.reference_logits`` (later PRs may
change the smoke): a cache-free full forward in fp32 — plain ``jax.numpy``,
no kernel, no cache, no batching — with every matmul at
``Precision.HIGHEST`` (on a TPU an fp32 matmul otherwise runs in lower
precision). It follows this repository's block, whose departures from GPT-2
the configuration file lists. Every sequence is padded to ``max_len`` so
that ONE compiled program scores every request: under the causal mask the
padded tail cannot reach an earlier position.
"""
import functools

GEN_MAX = 256           # most generated tokens one re-scored request has
# "same token" band for near-tied logits (chip_smoke's LOGIT_RTOL): with
# random weights the two largest logits of a position can lie closer than
# fp32 summation order moves them, so a served token counts as the
# reference's when its reference logit is within 1e-3 of the position's
# largest in units of that largest's magnitude. Computing the model in
# bf16 moves logits by about 1e-2 of that magnitude and fails the band.
LOGIT_RTOL = 1e-3
INIT_SCALE = 0.02       # serving/model.py random_params' scale


def model_config(cfg):
    from mxnet_tpu.serving import model as lm

    m = cfg["model"]
    return lm.ModelConfig(m["vocab"], m["num_layers"], m["model_dim"],
                          m["num_heads"], m["ffn_dim"], m["max_len"])


def init_params(cfg, seed):
    """The weights, made ON the device in one jitted call from the seed, in
    the type they are served in: the same shapes and scale as
    ``serving.model.random_params``, which draws them on the host."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import model as lm

    shapes = lm.param_shapes(model_config(cfg))
    dtype = jnp.dtype(cfg["weights_dtype"])

    def make(key):
        out = {}
        names = sorted(shapes)
        keys = jax.random.split(key, len(names))
        for k, name in zip(keys, names):
            if name.endswith("_gamma"):
                out[name] = jnp.ones(shapes[name], dtype)
            elif name.endswith(("_beta", "_bias")):
                out[name] = jnp.zeros(shapes[name], dtype)
            else:
                out[name] = (jax.random.normal(k, shapes[name], jnp.float32)
                             * INIT_SCALE).astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed)))


def _attention(q, k, v):
    """Plain causal attention, (H, S, hd) each, fp32."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    seq, hd = q.shape[1], q.shape[2]
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=hi) / jnp.sqrt(
        jnp.float32(hd))
    mask = jnp.tril(jnp.ones((seq, seq), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v, precision=hi)


def _reference(params, tokens, n_prompt, generated, num_layers, num_heads):
    """For each generated token j: the reference's logit of that token, the
    largest logit of its position, and the reference's own argmax.
    ``tokens`` is prompt + generated[:-1], zero-padded to max_len."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    seq = tokens.shape[0]

    def norm(x, name):
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return ((x - mean) / jnp.sqrt(var + 1e-5)
                * params[name + "_gamma"][0] + params[name + "_beta"][0])

    def heads(t):   # (S, M) -> (H, S, hd)
        return t.reshape(seq, num_heads, -1).transpose(1, 0, 2)

    x = params["embed_weight"][tokens] + params["pos_embed_weight"][0, :seq]
    for i in range(num_layers):
        p = "layer%d" % i
        qkv = jnp.dot(norm(x, p + "_ln1"), params[p + "_attn_in_weight"].T,
                      precision=hi)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        att = _attention(heads(q), heads(k), heads(v))
        att = att.transpose(1, 0, 2).reshape(seq, -1)
        x = x + jnp.dot(att, params[p + "_attn_out_weight"].T, precision=hi)
        f = jnp.dot(norm(x, p + "_ln2"), params[p + "_ffn1_weight"].T,
                    precision=hi) + params[p + "_ffn1_bias"]
        x = x + jnp.dot(jnp.maximum(f, 0), params[p + "_ffn2_weight"].T,
                        precision=hi) + params[p + "_ffn2_bias"]
    # position n_prompt-1+j of prompt+generated[:-1] scores token j
    rows = jnp.clip(n_prompt - 1 + jnp.arange(GEN_MAX), 0, seq - 1)
    x = jnp.take(norm(x, "final_ln"), rows, axis=0)
    logits = (jnp.dot(x, params["lm_head_weight"].T, precision=hi)
              + params["lm_head_bias"])
    chosen = jnp.take_along_axis(logits, generated[:, None], axis=1)[:, 0]
    return chosen, logits.max(-1), logits.argmax(-1)


def make_reference(cfg):
    """``score(params, prompt, generated) -> (off, argmax_matches)``:
    positions whose served token is outside the band, and how many served
    tokens are the reference's exact argmax."""
    import jax
    import numpy as np

    m = cfg["model"]
    fn = jax.jit(functools.partial(_reference, num_layers=m["num_layers"],
                                   num_heads=m["num_heads"]))

    def score(params, prompt, generated):
        n = len(generated)
        if n > GEN_MAX or len(prompt) + n > m["max_len"]:
            raise ValueError("request too long for the reference program")
        toks = np.zeros(m["max_len"], np.int32)
        seq = list(prompt) + list(generated[:-1])
        toks[:len(seq)] = seq
        gen = np.zeros(GEN_MAX, np.int32)
        gen[:n] = generated
        with jax.default_matmul_precision("highest"):
            chosen, top, arg = (np.asarray(a) for a in fn(
                params, toks, np.int32(len(prompt)), gen))
        chosen, top = chosen[:n].astype(np.float64), top[:n].astype(np.float64)
        off = [j for j in range(n)
               if abs(top[j] - chosen[j]) > LOGIT_RTOL * abs(top[j])]
        matches = int((arg[:n] == np.asarray(generated)).sum())
        return off, matches

    return score
