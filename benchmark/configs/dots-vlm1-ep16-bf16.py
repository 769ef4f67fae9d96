"""The language model of dots.vlm1.inst (rednote-hilab/dots.vlm1.inst; key for
key DeepSeek-V3's block, arXiv:2412.19437 sections 2.1.1 and 2.1.2; YaRN,
arXiv:2309.00071) served by ``ServingEngine`` in bfloat16 as ONE RANK of an
expert-parallel layout; and its plain reference, given the same share.

The model (0-indexed layer ``l``; RMSNorm is gamma only, statistics in
float32), every layer ``x = x + MLA_l(RMSNorm(x))`` then
``x = x + FFN_l(RMSNorm(x))``; a final RMSNorm; logits ``x W_head^T``:

    MLA   c_q = RMSNorm(W_dq h);  q = W_uq c_q, a head is [q_n | q_r]
          [c | k_r] = W_dkv h;  c = RMSNorm(c);  k_r = RoPE(k_r), ONE rotary
          key a token under every head;  q_r = RoPE(q_r) a head
          [k_n | v] = W_ukv c a head
          s_ij = (q_n,i . k_n,j + q_r,i . k_r,j) (dn + dr)^-0.5 m^2, causal,
          m = 0.1 mscale_all_dim ln(factor) + 1;  o = softmax(s) v
          MLA = W_o [o_1 .. o_H]
          RoPE is YaRN's: inv_freq = f / factor where the lane turns less
          than beta_slow times over the original length, f where it turns
          more than beta_fast times, a linear ramp between
    FFN   l < first_dense:  W_down (silu(W_gate h) * (W_up h))
          else  s = sigmoid(W_r h) over ALL experts, float32; the choice on
          s' = s + b: the experts are n_group groups, a group's score the
          sum of its two largest s', the topk_group best groups stay (the
          others' s' read 0, as modeling_deepseek.py fills them), the k
          largest s' among them are the token's experts T; the weights from
          s: w_e = scale s_e / (sum_T s + 1e-20);
          FFN = sum_{e in T, e held here} w_e E_e(h) + E_shared(h)

The reference computes exactly that in float32 on the served weights cast
up, one matrix at a time — plain ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: NON-absorbed attention with a
dense causal mask (eight heads at a time), the router's selection spelled
out with sorts, the experts as a loop over the held ones with a mask, the
dense FFN in slices of its width; no kernel, no cache, no batching, and
nothing imported from ``ops/`` or ``serving/`` (``serving_config`` and
``init_params`` are the driver's, not the reference's). What the absent
experts would add is left out here as in the program. Every sequence is
padded to ``reference.seq_pad`` so that ONE compiled program scores every
request.
"""
import functools

# Four bands, this configuration's own, all set from the chip at the
# published widths (PERF.md sections 4 and 6, PR 33, has the readings) and
# all over the weights the driver drew.
#
# PROBE_RTOL bounds the dense comparison of ``make_probe``: a row's error is
# its largest served-minus-reference logit in units of the row's largest
# reference logit. The prefilled rows are prefixes of a seeded text, from the
# mix's shortest prompt to past its longest (every prefill bucket; expanded
# attention, the flash forward); the decoded rows are as many cuts of the
# same text as the engine has lanes, each prefilled to its cut and then
# forced through the decode program TOGETHER, ragged contexts side by side
# up to the text's length (the latent cache, the absorbed form, the
# ``latent_paged`` kernel over one to five fetches, the experts at a decode
# step's load). Each half has its first quartile and the LARGER is what the
# band bounds, so that a fault of the decode path alone cannot hide behind
# sound prefills.
#
# PROBE_MEDIAN_RTOL bounds the MEDIAN of all those rows. A fault that moves
# a part of the rows by much leaves the first quartile among the rows it
# did not touch and moves the median: the routed weights not scaled by 2.5
# (the held experts' part is a sixteenth of the routed sum) read 1.8% at the
# quartile and 7.7-7.9% at the median, the group limit ignored 1.7-1.9% and
# 8.3-8.5%, against sound's 1.5-1.6% at both; sound serving's own flipped
# expert choices (rows at 8-18%) are too few to reach it. The limit is the
# geometric middle of sound's largest median and the faults' smallest.
#
# PROBE_HELD_RTOL bounds the same larger first quartile of the HELD PASS:
# the same rows served and scored again over the same weights but for data
# (:func:`held_pass`) — the router's correction bias sends every token's
# choice to the experts held here, in the last expert layers past rival
# groups that the group limit must weigh, and the down-projections of what
# every rank computes whole are scaled down by a power of two — so that the
# held experts carry the residual stream, at the load a deployment's
# exchange would bring them. As served, this rank's routed part is a
# sixteenth of the routed sum beside a shared expert, a dense layer and
# attention, and held experts in float8 (1.6-1.8%) or one of them zeroed
# (1.5-1.6%) pass the two bands above; in the held pass they read 9.6-10.6%
# and 21-51% against sound's 1.5-1.7%, the group limit ignored 47-52%. The
# limit is the geometric middle of sound's largest and float8's smallest.
#
# LOGIT_RTOL is the "same token" band of ``make_reference``: a served token
# counts as the reference's when its reference logit is within LOGIT_RTOL of
# the position's largest, in units of that largest's magnitude.
LOGIT_RTOL = 3e-1
PROBE_RTOL = 2.2e-2
PROBE_MEDIAN_RTOL = 3.5e-2
PROBE_HELD_RTOL = 4e-2


def serving_config(cfg):
    """The ``ServingConfig`` of this configuration file: its ``model`` and
    ``engine`` objects, as ``tools/serve.py --model-config`` reads them."""
    from mxnet_tpu.serving import ServingConfig

    return ServingConfig.from_json(cfg)


def init_params(cfg, seed):
    """The weights, made ON the device from the seed in the type they are
    served in: N(0, ``init.std``), gammas 1, the router's correction bias
    N(0, ``init.router_bias_std``); the routed and shared experts' stacks
    times ``init.expert_gain``. One small program per distinct shape, so
    that no more than one array's float32 draw is alive at a time."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import model as lm

    shapes = lm.param_shapes(serving_config(cfg))
    dtype = jnp.dtype(cfg["weights_dtype"])
    init = cfg.get("init", {})
    std = init.get("std", 0.02)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        if name.endswith("_gamma"):
            out[name] = jnp.ones(shapes[name], dtype)
            continue
        scale = std
        if name.endswith("_router_bias"):
            scale = init.get("router_bias_std", 0.01)
        elif "_experts_" in name or "_shared_" in name:
            scale = std * init.get("expert_gain", 1.0)
        out[name] = draw(jax.random.fold_in(key, i), shapes[name],
                         float(scale))
    return out


# ------------------------------------------------------------ reference --
_HEADS_AT_A_TIME = 8
_DENSE_SLICES = 8


def _rms(t, gamma, eps):
    import jax.numpy as jnp

    return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps) * gamma


def _yarn(m):
    """``(inv_freq (dr / 2,), cos/sin scale, softmax scale)`` of the rotary
    lanes, spelled out from the published numbers."""
    import numpy as np

    dr, theta = m["rope_dim"], float(m["rope_theta"])
    scale = float(m["head_dim"] + dr) ** -0.5
    f = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    if not m.get("rope_yarn"):
        return f.astype(np.float32), 1.0, scale
    factor, orig, fast, slow, mscale, mscale_all = m["rope_yarn"]

    def lane_of(turns):     # the lane pair that turns `turns` times in orig
        return dr * np.log(orig / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(lane_of(fast)), 0.0)
    high = min(np.ceil(lane_of(slow)), dr - 1.0)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dr // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1: the lane keeps its frequency
    inv = f / factor * (1.0 - keep) + f * keep

    def mag(ms):
        return 0.1 * ms * np.log(factor) + 1.0 if factor > 1 else 1.0

    if mscale_all:
        scale *= mag(mscale_all) ** 2
    return inv.astype(np.float32), float(mag(mscale) / mag(mscale_all)), scale


def _rope(t, inv, mag):
    """(S, H, d) at positions 0..S-1: t cos + rotate_half(t) sin."""
    import jax.numpy as jnp

    seq, _, d = t.shape
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None]          # (S, 1, d)
    half = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], -1)
    return (t * jnp.cos(emb) + half * jnp.sin(emb)) * mag


def _mla(h, w, m):
    """Non-absorbed latent attention of ``h`` (S, M): every head's keys and
    values expanded from the latent, dense causal softmax."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    seq = h.shape[0]
    heads, dn, dr, dv = (m["num_heads"], m["head_dim"], m["rope_dim"],
                         m["v_dim"])
    eps = m["norm_eps"]
    inv, mag, scale = _yarn(m)
    c_q = _rms(jnp.dot(h, w("_mla_q_down_weight").T, precision=hi),
               w("_mla_q_norm_gamma"), eps)
    q = jnp.dot(c_q, w("_mla_q_up_weight").T, precision=hi).reshape(
        seq, heads, dn + dr)
    ckr = jnp.dot(h, w("_mla_kv_down_weight").T, precision=hi)
    c = _rms(ckr[:, :m["kv_rank"]], w("_mla_kv_norm_gamma"), eps)
    k_r = _rope(ckr[:, None, m["kv_rank"]:], inv, mag)[:, 0]    # (S, dr)
    kv = jnp.dot(c, w("_mla_kv_up_weight").T, precision=hi).reshape(
        seq, heads, dn + dv)
    q_r = _rope(q[..., dn:], inv, mag)
    causal = jnp.tril(jnp.ones((seq, seq), bool))[None]

    def some_heads(xs):
        q_n, q_rot, k_n, v = xs                 # (S, g, .) each
        s = (jnp.einsum("qhd,khd->hqk", q_n, k_n, precision=hi)
             + jnp.einsum("qhd,kd->hqk", q_rot, k_r, precision=hi)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=hi)

    def grouped(t):                             # (S, H, d) -> (H/g, S, g, d)
        g = _HEADS_AT_A_TIME
        return t.reshape(seq, heads // g, g, -1).transpose(1, 0, 2, 3)

    o = jax.lax.map(some_heads, (grouped(q[..., :dn]), grouped(q_r),
                                 grouped(kv[..., :dn]), grouped(kv[..., dn:])))
    o = o.transpose(1, 0, 2, 3).reshape(seq, heads * dv)
    return jnp.dot(o, w("_attn_out_weight").T, precision=hi)


def _gated(h, gate, up, down):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    return jnp.dot(jax.nn.silu(jnp.dot(h, gate.astype(f32).T, precision=hi))
                   * jnp.dot(h, up.astype(f32).T, precision=hi),
                   down.astype(f32).T, precision=hi)


def _dense(h, params, p, m):
    """The leading layers' FFN, a slice of its width at a time."""
    import jax
    import jax.numpy as jnp

    n = _DENSE_SLICES if m["dense_ffn_dim"] % _DENSE_SLICES == 0 else 1
    width = m["dense_ffn_dim"] // n
    w1 = params[p + "_ffn1_weight"].reshape(2, n, width, -1)   # [gate; up]
    w2 = params[p + "_ffn2_weight"].reshape(-1, n, width).transpose(1, 0, 2)

    def one(acc, xs):
        gate, up, down = xs
        return acc + _gated(h, gate, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (w1[0], w1[1], w2))
    return out


def choose(scores, bias, m):
    """The token's experts and their weights, ``(S, E)`` with zeros for the
    experts not chosen: the selection spelled out with sorts (a stable
    descending order: of equals the lower index first)."""
    import jax.numpy as jnp

    seq, e = scores.shape
    k, scale = m["experts_per_tok"], m.get("route_scale", 1.0)
    n_group, topk_group = m.get("n_group", 1), m.get("topk_group", 1)
    biased = scores + bias
    groups = biased.reshape(seq, n_group, e // n_group)
    best_two = jnp.sort(groups, -1)[..., -min(2, e // n_group):].sum(-1)
    order = jnp.argsort(-best_two, -1, stable=True)             # (S, G)
    rank = jnp.argsort(order, -1, stable=True)      # a group's place
    kept = jnp.repeat(rank < topk_group, e // n_group, axis=1)  # (S, E)
    masked = jnp.where(kept, biased, 0.0)
    top = jnp.argsort(-masked, -1, stable=True)[:, :k]          # (S, k)
    chosen = jnp.zeros((seq, e), bool).at[
        jnp.arange(seq)[:, None], top].set(True)
    picked = jnp.where(chosen, scores, 0.0)
    return picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale


def _experts(h, params, p, m):
    """Router over all experts; the sum over the chosen ones HELD HERE, one
    expert at a time with a mask; the shared expert whole."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(
        h, params[p + "_router_weight"].astype(f32).T, precision=hi))
    weight = choose(scores, params[p + "_router_bias"].astype(f32), m)
    first, count = m.get("experts_held") or (0, m["num_experts"])
    here = weight[:, first:first + count]

    def one(acc, xs):
        gate, up, down, w = xs
        return acc + w[:, None] * _gated(h, gate, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        params[p + "_experts_gate_weight"], params[p + "_experts_up_weight"],
        params[p + "_experts_down_weight"], here.T))
    if m.get("shared_experts"):
        out = out + _gated(h, params[p + "_shared_gate_weight"],
                           params[p + "_shared_up_weight"],
                           params[p + "_shared_down_weight"])
    return out


def _hidden(params, tokens, m):
    """The final-normed hidden state (S, M) of ``tokens`` (S,), fp32."""
    import jax.numpy as jnp

    eps = m["norm_eps"]
    x = params["embed_weight"][tokens].astype(jnp.float32)
    for i in range(m["num_layers"]):
        p = "layer%d" % i

        def w(name, p=p):
            return params[p + name].astype(jnp.float32)

        x = x + _mla(_rms(x, w("_ln1_gamma")[0, 0], eps), w, m)
        n2 = _rms(x, w("_ln2_gamma")[0, 0], eps)
        x = x + (_dense(n2, params, p, m) if i < m.get("first_dense", 0)
                 else _experts(n2, params, p, m))
    return _rms(x, params["final_ln_gamma"].astype(jnp.float32)[0, 0], eps)


def _head(x, params):
    import jax
    import jax.numpy as jnp

    return jnp.dot(x, params["lm_head_weight"].astype(jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)


def _logits(params, tokens, m):
    return _head(_hidden(params, tokens, m), params)


def _score(params, tokens, n_prompt, generated, m):
    """For each generated token j: the reference's logit of that token, the
    largest logit of its position, and the reference's own argmax.
    ``tokens`` is prompt + generated[:-1], zero-padded."""
    import jax.numpy as jnp

    x = _hidden(params, tokens, m)
    # position n_prompt-1+j of prompt+generated[:-1] scores token j
    rows = jnp.clip(n_prompt - 1 + jnp.arange(generated.shape[0]), 0,
                    tokens.shape[0] - 1)
    logits = _head(jnp.take(x, rows, axis=0), params)
    chosen = jnp.take_along_axis(logits, generated[:, None], axis=1)[:, 0]
    return chosen, logits.max(-1), logits.argmax(-1)


def _rows_logits(params, tokens, rows, m):
    """The reference's logits (K, V) at positions ``rows`` of ``tokens``."""
    import jax.numpy as jnp

    return _head(jnp.take(_hidden(params, tokens, m), rows, axis=0), params)


def probe_plan(cfg, seed):
    """The probe's rows over one text of ``reference.probe_len`` tokens,
    from the seed: ``(prefixes, lanes)``. ``prefixes``: the lengths that
    are prefilled and read, ``reference.probe_prefixes`` = [shortest, how
    many], spread by equal ratios up to the text's length. ``lanes``:
    ``reference.probe_lanes`` pairs ``(n, start)``, a prefill of ``start``
    tokens followed by ``n - start`` forced decode steps,
    ``reference.probe_decode`` = [fewest, most], the cuts uniform over the
    text."""
    import numpy as np

    ref = cfg["reference"]
    length = ref["probe_len"]
    shortest, k = ref["probe_prefixes"]
    lo, hi = ref["probe_decode"]
    rng = np.random.RandomState((seed + 1) % 2 ** 32)
    prefixes = [int(n) for n in np.unique(
        np.geomspace(shortest, length, k).round().astype(np.int32))]
    lanes = []
    for _ in range(ref["probe_lanes"]):
        steps = int(rng.randint(lo, hi + 1))
        start = int(rng.randint(1, length - steps + 1))
        lanes.append((start + steps, start))
    return prefixes, lanes


class held_pass:
    """For as long as it is entered, the weights in ``holders`` (dicts
    that hold the served arrays: the driver's and the engine's) are those
    of the probe's held pass, ``reference.held_pass`` of the configuration
    file; data only, and on leaving every array is what it was, bit for bit:

    * ``held_bias`` is added to the router's correction bias of the experts
      held here, in every expert layer: larger than any score, so every
      token's k experts are among them (and a held expert meets half of
      the tokens where half are chosen);
    * in the last ``rival_layers`` expert layers ``rival_bias`` is added to
      the first expert of each of the ``topk_group + 1`` groups behind the
      held experts' own: each of those groups beats the held experts' group
      or not with the token's score for its one favoured expert, so the
      group limit keeps the held experts' group for about half of the
      tokens (k less ``topk_group - 1`` of their experts are then held
      here) and drops it for the others (none is), where a plain top-k
      takes k less ``topk_group + 1`` of them for every token;
    * the attention's output projection, the dense layers' down-projection
      and the shared expert's are scaled by ``2 ** whole_log2``: what every
      rank computes whole then weighs little beside the held experts' sum.

    One array at a time: a second copy of the weights does not fit."""

    def __init__(self, cfg, *holders):
        self.cfg, self.holders, self.kept = cfg, holders, []

    def _change(self, name, fn):
        made = {}
        for d in self.holders:
            old = d[name]
            if id(old) not in made:
                made[id(old)] = fn(old)
            d[name] = made[id(old)]

    def _scale(self, by):
        m = self.cfg["model"]
        for i in range(m["num_layers"]):
            p = "layer%d" % i
            names = [p + "_attn_out_weight",
                     p + ("_ffn2_weight" if i < m.get("first_dense", 0)
                          else "_shared_down_weight")]
            for name in names:
                self._change(name, lambda w: w * by)

    def __enter__(self):
        import numpy as np

        m, h = self.cfg["model"], self.cfg["reference"]["held_pass"]
        first, count = m["experts_held"]
        size = m["num_experts"] // m["n_group"]
        extra = np.zeros(m["num_experts"], np.float32)
        extra[first:first + count] = h["held_bias"]
        rivals = extra.copy()
        own = first // size
        for g in range(1, m["topk_group"] + 2):
            rivals[((own + g) % m["n_group"]) * size] += h["rival_bias"]
        layers = list(range(m.get("first_dense", 0), m["num_layers"]))
        for j, i in enumerate(layers):
            name = "layer%d_router_bias" % i
            add = rivals if j >= len(layers) - h["rival_layers"] else extra
            self.kept.append((name, [d[name] for d in self.holders]))
            self._change(name, lambda b, add=add: (
                b.astype(np.float32) + add).astype(b.dtype))
        self._scale(2.0 ** h["whole_log2"])
        return self

    def __exit__(self, *exc):
        self._scale(2.0 ** -self.cfg["reference"]["held_pass"]["whole_log2"])
        for name, was in self.kept:
            for d, b in zip(self.holders, was):
                d[name] = b
        self.kept = []


def make_probe(cfg):
    """``probe(params, eng, seed, before_serving=None) -> {"quartile",
    "median", "worst", "rows", "prefill_quartile", "decode_quartile",
    "held": {the same}}``: the served next-token logits of ``eng`` (a
    ``ServingEngine`` over ``params``: its ``prefill_logits`` and
    ``decode_logits``) against the reference's over ``params`` at the rows
    of :func:`probe_plan` of one seeded random text, as served and again
    inside :class:`held_pass`. ``prefill_quartile`` and
    ``decode_quartile`` are the first quartiles of the two halves, and
    ``quartile``, which ``PROBE_RTOL`` (``PROBE_HELD_RTOL``) bounds, is the
    LARGER of them. The reference's rows of both passes are computed first;
    ``before_serving()`` is then called (a study plants a fault of the
    weights there)."""
    import jax
    import numpy as np

    length = cfg["reference"]["probe_len"]
    fn = jax.jit(functools.partial(_rows_logits, m=cfg["model"]))

    def summary(got, want, decoded):
        errors = np.abs(got - want).max(-1) / np.abs(want).max(-1)

        def q1(e):
            return float(np.percentile(e, 25))

        halves = q1(errors[~decoded]), q1(errors[decoded])
        return {"quartile": max(halves), "median": float(np.median(errors)),
                "third_quartile": float(np.percentile(errors, 75)),
                "worst": float(errors.max()), "rows": len(errors),
                "prefill_quartile": halves[0], "decode_quartile": halves[1]}

    def probe(params, eng, seed, before_serving=None):
        prefixes, lanes = probe_plan(cfg, seed)
        text = np.random.RandomState(seed % 2 ** 32).randint(
            0, cfg["model"]["vocab"], length).astype(np.int32)
        ends = np.asarray(prefixes + [n for n, _ in lanes], np.int32)
        decoded = np.arange(len(ends)) >= len(prefixes)

        def holders():
            return (params,) if eng.params is params \
                else (params, eng.params)

        def reference():
            with jax.default_matmul_precision("highest"):
                return np.asarray(fn(params, text, ends - 1))

        def served():
            return np.concatenate([
                np.stack([eng.prefill_logits(text[:n]) for n in prefixes]),
                eng.decode_logits([text[:n] for n, _ in lanes],
                                  [start for _, start in lanes])])

        want = reference()
        with held_pass(cfg, *holders()):
            want_held = reference()
        if before_serving is not None:
            before_serving()
        out = summary(served(), want, decoded)
        with held_pass(cfg, *holders()):
            out["held"] = summary(served(), want_held, decoded)
        return out

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
