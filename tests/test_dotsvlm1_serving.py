"""dots.vlm1's language model (DeepSeek-V3's block: latent attention over a
one-latent cache, sigmoid-routed experts in groups beside a shared expert,
a leading dense layer; ONE RANK'S SHARE of the experts) through
``ServingEngine``, against the plain fp32 reference of
``benchmark/configs/dots-vlm1-ep16-bf16.py`` — logits, not tokens — at a
small size on the CPU: 1 dense + 2 expert layers, 64 wide, 8 heads of 16 + 8
rotary lanes, latent 128, 16 experts in 4 groups of which 2, 4 a token, 4
held; blocks of 16.

* every depth, prefill and prefill-then-decode against the reference's full
  forward; the benchmark's own probe and scorer over the same engine;
* ``route`` against a spelled-out selection (ties, a group with one
  candidate); the shares of all ranks and the shared expert once add up to
  the uncut layer, in the program and in the reference;
* absorbed decode = expanded attention; the decode chunk = single steps;
* recompute preemption, concurrent = sequential; the counters' identities;
* the wrong servers of ``tools/wrong_servers.py`` are far from the reference;
* ``ServingConfig``'s refusals.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import moe
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving import model as M
from mxnet_tpu.serving.scheduler import FINISHED

from chunk_cases import chunk_equals_single_steps, lane, tables_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from tools import wrong_servers as W  # noqa: E402

_CFG, C = W.load_config(os.path.join(
    ROOT, "benchmark", "rehearsal", "configs", "dotsvlm1-tiny.json"))
VOCAB, BS = 211, 16


def tiny(dtype="float32", **model):
    """A configuration file's worth of the tiny model."""
    cfg = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in _CFG.items()}
    cfg["model"].update(vocab=VOCAB, **model)
    cfg["engine"]["kv_dtype"] = cfg["weights_dtype"] = dtype
    cfg["init"] = dict(cfg["init"], std=0.2 if dtype == "float32" else 0.113)
    return cfg


@pytest.fixture(scope="module")
def served():
    """(cfg, params, engine, a 150-token text, the reference's logits)"""
    cfg = tiny()
    params = C.init_params(cfg, 3)
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=3)
    text = np.random.RandomState(0).randint(0, VOCAB, 150).astype(np.int32)
    return cfg, params, eng, text, C.reference_logits(cfg)(params, text)


def _err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _drain(eng):
    while eng.has_work():
        eng.step()


# ------------------------------------------------------- against the oracle
@pytest.mark.parametrize("n", [5, 16, 40, 100, 150])
def test_prefill_logits_are_the_references(served, n):
    _cfg, _params, eng, text, want = served
    assert _err(eng.prefill_logits(text[:n]), want[n - 1]) < 1e-4


@pytest.mark.parametrize("n,start", [(40, 20), (100, 20), (150, 10),
                                     (150, 70), (49, 48)])
def test_prefill_then_decode_through_the_latent_cache(served, n, start):
    """``start`` tokens through prefill (expanded heads) into a scratch
    stream, the rest one by one through the decode program (the absorbed
    form over the cached latents and rotated keys)."""
    _cfg, _params, eng, text, want = served
    used = eng.pool.used()
    got = eng.prefill_logits(text[:n], decode_from=start)
    assert _err(got, want[n - 1]) < 1e-4
    assert eng.pool.used() == used


@pytest.mark.parametrize("layers,first_dense", [(1, 0), (2, 1), (2, 0),
                                                (3, 2)])
def test_every_depth_against_the_reference(layers, first_dense):
    """Models of the first layers only, dense or with experts: each layer
    in its place against the reference's."""
    cfg = tiny(num_layers=layers, first_dense=first_dense,
               layer_kinds=["mla"] * layers)
    params = C.init_params(cfg, 4)
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=4)
    text = np.random.RandomState(1).randint(0, VOCAB, 40).astype(np.int32)
    want = C.reference_logits(cfg)(params, text)
    assert _err(eng.prefill_logits(text), want[-1]) < 1e-4
    assert _err(eng.prefill_logits(text, decode_from=30), want[-1]) < 1e-4


def test_generated_tokens_through_chunks_are_the_references(served):
    """Prefill, then decode chunks of 8 through the cache: the tokens are
    the reference's argmax at every position."""
    cfg, params, eng, _text, _want = served
    assert eng._chunk == 8
    prompt = list(range(1, 45))
    out = eng.generate([prompt], 40)[0]
    off, matches = C.make_reference(cfg)(params, prompt, out)
    assert off == [] and matches >= 39


def test_the_cells_probe_runs_over_the_engine(served):
    """Prefilled rows, rows decoded side by side in the engine's lanes, and
    the same again in the held pass; the weights come back bit for bit."""
    cfg, params, eng, _text, _want = served
    was = {k: np.asarray(v) for k, v in params.items()}
    seen = C.make_probe(cfg)(params, eng, 7)
    assert seen["rows"] == 8 and seen["worst"] < 1e-4
    assert seen["held"]["rows"] == 8 and seen["held"]["worst"] < 1e-4
    prefixes, lanes = C.probe_plan(cfg, 7)
    assert prefixes == [12, 24, 48, 96] and len(lanes) == 4
    assert all(8 <= n - s <= 24 and n <= 96 for n, s in lanes)
    for holder in (params, eng.params):
        assert all((np.asarray(holder[k]) == v).all()
                   for k, v in was.items())


def test_lanes_decoded_together_are_lanes_decoded_alone(served):
    """``decode_logits``: ragged contexts side by side, a lane dead once its
    text has ended, against each text through ``prefill_logits``."""
    _cfg, _params, eng, text, want = served
    cuts = [(150, 70), (40, 20), (100, 93)]
    used = eng.pool.used()
    got = eng.decode_logits([text[:n] for n, _ in cuts],
                            [s for _, s in cuts])
    assert eng.pool.used() == used
    for row, (n, s) in zip(got, cuts):
        assert _err(row, want[n - 1]) < 1e-4
        assert _err(row, eng.prefill_logits(text[:n], decode_from=s)) < 1e-5
    with pytest.raises(ValueError, match="decode_from must be in"):
        eng.decode_logits([text[:10]], [10])
    with pytest.raises(ValueError, match="need 1..max_batch"):
        eng.decode_logits([text[:10]] * 5, [5] * 5)


def test_in_the_held_pass_every_choice_falls_on_the_held_experts():
    """The held pass's data: every token's experts are held here in the
    first expert layer; in the last the group limit keeps the held experts'
    group for some tokens and drops it for others, where a plain top-k
    takes another count for every token."""
    cfg = tiny()
    m = cfg["model"]
    params = C.init_params(cfg, 3)
    rng = np.random.RandomState(0)
    scores = jax.nn.sigmoid(jnp.asarray(rng.randn(400, 16) * 1.7,
                                        jnp.float32))

    def held(layer, **model):
        bias = params["layer%d_router_bias" % layer].astype(jnp.float32)
        w = np.asarray(C.choose(scores, bias, dict(m, **model)))
        return (w[:, :4] > 0).sum(1)

    assert (held(1) < 4).any()
    with C.held_pass(cfg, params):
        assert (held(1) == 4).all()
        kept = held(2)
        assert set(kept) == {0, 3} and 0.2 < (kept == 3).mean() < 0.8
        assert (held(2, n_group=1, topk_group=1) == 1).all()
        assert float(jnp.abs(params["layer0_ffn2_weight"]).max()) < 0.1
    assert (held(1) < 4).any()


def test_bfloat16_serving_stays_near_the_reference():
    cfg = tiny("bfloat16")
    params = C.init_params(cfg, 5)
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=5)
    seen = C.make_probe(cfg)(params, eng, 5)
    assert seen["quartile"] < 0.08, seen


# ------------------------------------------------------------------ router
def _spelled_out(scores, bias, k, n_group, topk_group, scale):
    """numpy, one token at a time; of equals the lower index first."""
    weights, experts = [], []
    for s in scores:
        c = s + bias
        per = len(c) // n_group
        group = [np.sort(c[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(n_group)]
        keep = sorted(range(n_group), key=lambda g: (-group[g], g))
        keep = set(keep[:topk_group])
        masked = [c[e] if e // per in keep else 0.0 for e in range(len(c))]
        top = sorted(range(len(c)), key=lambda e: (-masked[e], e))[:k]
        w = s[top]
        weights.append(w / (w.sum() + 1e-20) * scale)
        experts.append(top)
    return np.asarray(weights), np.asarray(experts)


def _scores_to_inputs(scores):
    """(x, router) whose sigmoid(router . x) is ``scores`` (T, E)."""
    logit = np.log(scores / (1 - scores)).astype(np.float32)
    return jnp.asarray(logit), jnp.eye(scores.shape[1], dtype=jnp.float32)


@pytest.mark.parametrize("case", ["random", "ties", "one_candidate"])
def test_route_is_the_spelled_out_selection(case):
    rng = np.random.RandomState(2)
    scores = rng.uniform(0.05, 0.95, (12, 16)).astype(np.float32)
    bias = (rng.randn(16) * 0.01).astype(np.float32)
    if case == "ties":          # equal scores inside and across groups
        scores[:] = np.round(scores * 4) / 4 * 0.9 + 0.05
        bias[:] = 0
    if case == "one_candidate":     # a group whose others are all but zero
        scores[:, 4:8] = 1e-4
        scores[:, 5] = 0.94
    x, router = _scores_to_inputs(scores)
    w, e = moe.route(x, router, 4, bias=jnp.asarray(bias),
                     kind="sigmoid_group", n_group=4, topk_group=2,
                     scale=2.5)
    got_scores = np.asarray(jax.nn.sigmoid(x))
    want_w, want_e = _spelled_out(got_scores, bias, 4, 4, 2, 2.5)
    np.testing.assert_array_equal(np.asarray(e), want_e)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # the reference's own selection is the same one
    m = dict(experts_per_tok=4, n_group=4, topk_group=2, route_scale=2.5)
    dense = np.asarray(C.choose(jnp.asarray(got_scores), jnp.asarray(bias),
                                m))
    np.testing.assert_allclose(
        np.take_along_axis(dense, want_e, axis=1), want_w, rtol=1e-6)
    assert (dense > 0).sum(-1).tolist() == [4] * 12


def test_softmax_route_is_what_it_was():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(9, 32), jnp.float32)
    router = jnp.asarray(rng.randn(8, 32), jnp.float32)
    w, e = moe.route(x, router, 2)
    probs = np.asarray(jax.nn.softmax(x @ router.T))
    np.testing.assert_array_equal(np.asarray(e), np.argsort(-probs)[:, :2])
    np.testing.assert_allclose(np.asarray(w), -np.sort(-probs)[:, :2],
                               rtol=1e-6)


# ------------------------------------------------------------- the shares
def _layer_params(cfg, seed):
    params = C.init_params(cfg, seed)
    return {k[len("layer1"):]: v for k, v in params.items()
            if k.startswith("layer1_")}


def test_the_shares_add_up():
    """An expert layer computed by four ranks of four experts each, plus
    the shared expert ONCE, is the uncut layer: in the program
    (``moe_ffn(held=...)``) and in the reference; and a rank's part is the
    reference's part of that rank."""
    cfg = tiny(experts_held=None)
    m = cfg["model"]
    p = _layer_params(cfg, 6)
    h = jnp.asarray(np.random.RandomState(6).randn(24, 64), jnp.float32)
    how = dict(kind="sigmoid_group", bias=p["_router_bias"], n_group=4,
               topk_group=2, scale=2.5)
    stacks = [p["_experts_%s_weight" % n] for n in ("gate", "up", "down")]
    uncut, load = moe.moe_ffn(h, p["_router_weight"], *stacks, 4, **how)
    assert int(load.sum()) == 4 * 24

    def ref_layer(m, params):
        return np.asarray(C._experts(
            h, {"layer1" + k: v for k, v in params.items()}, "layer1", m))

    with jax.default_matmul_precision("highest"):
        want = ref_layer(dict(m, shared_experts=0), p)
        parts, ref_parts = [], []
        for rank in range(4):
            held = (4 * rank, 4)
            mine = [s[held[0]:held[0] + 4] for s in stacks]
            part, rank_load = moe.moe_ffn(h, p["_router_weight"], *mine, 4,
                                          held=held, **how)
            np.testing.assert_array_equal(np.asarray(rank_load),
                                          np.asarray(load))
            parts.append(np.asarray(part))
            ref_parts.append(ref_layer(
                dict(m, shared_experts=0, experts_held=list(held)),
                dict(p, **{"_experts_%s_weight" % n: w for n, w in zip(
                    ("gate", "up", "down"), mine)})))
            np.testing.assert_allclose(parts[-1], ref_parts[-1], atol=2e-5)
        shared = np.asarray(M._gated(h, p["_shared_gate_weight"],
                                     p["_shared_up_weight"],
                                     p["_shared_down_weight"], None))
        whole = ref_layer(m, p)
    np.testing.assert_allclose(sum(parts), np.asarray(uncut), atol=2e-5)
    np.testing.assert_allclose(sum(ref_parts), want, atol=2e-5)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=5e-5)
    assert np.abs(whole).max() > 10 * 5e-5


def test_a_share_with_no_pair_here_adds_nothing():
    """Every token's experts lie on other ranks: the grouped matmuls visit
    no row, and what they leave behind is not added."""
    cfg = tiny(experts_held=None)
    p = _layer_params(cfg, 8)
    h = jnp.asarray(np.random.RandomState(8).randn(8, 64), jnp.float32)
    bias = jnp.zeros(16).at[:4].set(-10.0)      # never the first group
    stacks = [p["_experts_%s_weight" % n][:4] for n in ("gate", "up", "down")]
    out, load = moe.moe_ffn(h, p["_router_weight"], *stacks, 4, held=(0, 4),
                            kind="sigmoid_group", bias=bias, n_group=4,
                            topk_group=2, scale=2.5)
    assert int(load[:4].sum()) == 0 and int(load.sum()) == 32
    np.testing.assert_array_equal(np.asarray(out), 0)


# ---------------------------------------------------------------- attention
def test_absorbed_decode_is_expanded_attention():
    """``softmax((q_n W_uk) c + q_r k_r) c W_uv`` over the cached rows is
    ``softmax(q_n k_n + q_r k_r) v`` over the expanded heads."""
    rng = np.random.RandomState(9)
    b, h, dn, dr, dv, c, bs, nb = 3, 8, 16, 8, 16, 128, 16, 4
    ctx = np.array([50, 1, 64], np.int32)
    lat = jnp.asarray(rng.randn(b, nb * bs, c), jnp.float32)
    kr = jnp.asarray(rng.randn(b, nb * bs, dr), jnp.float32)
    w_uk = jnp.asarray(rng.randn(h, dn, c) * 0.1, jnp.float32)
    w_uv = jnp.asarray(rng.randn(h, dv, c) * 0.1, jnp.float32)
    qn = jnp.asarray(rng.randn(b, h, dn), jnp.float32)
    qr = jnp.asarray(rng.randn(b, h, dr), jnp.float32)
    # a pool whose blocks are each stream's rows in order, block 0 trash
    tables = 1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb)
    c_pages = jnp.concatenate([jnp.zeros((1, 1, bs, c)),
                               lat.reshape(b * nb, 1, bs, c)])
    r_pages = jnp.concatenate([jnp.zeros((1, 1, bs, 128)), M._pad_lanes(
        kr, 128).reshape(b * nb, 1, bs, 128)])
    with jax.default_matmul_precision("highest"):
        out = A.latent_paged(jnp.einsum("bhd,hdc->bhc", qn, w_uk),
                             M._pad_lanes(qr, 128), c_pages, r_pages, tables,
                             ctx, 0.2)
        got = jnp.einsum("bhc,hdc->bhd", out, w_uv)
        kn = jnp.einsum("btc,hdc->bthd", lat, w_uk)
        v = jnp.einsum("btc,hdc->bthd", lat, w_uv)
        s = (jnp.einsum("bhd,bthd->bht", qn, kn)
             + jnp.einsum("bhd,btd->bht", qr, kr)) * 0.2
        seen = jnp.arange(nb * bs)[None, None] < ctx[:, None, None]
        want = jnp.einsum("bht,bthd->bhd", jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_yarn_frequencies_and_scale_are_the_published_ones():
    """factor 40 over 4096 original positions, 64 rotary lanes: the fast
    lanes keep their frequency, the slow ones are divided by 40; the
    softmax scale carries ``(0.1 ln 40 + 1)^2``. The program's numbers are
    the reference's."""
    cfg = M.ModelConfig(97, 1, 64, 8, 32, 256, norm="rms", pos="rope",
                        bias=False, head_dim=128, layer_kinds=["mla"],
                        q_rank=48, kv_rank=128, rope_dim=64, v_dim=128,
                        rope_yarn=(40, 4096, 32, 1, 1, 1))
    inv, mag = M.mla_rope(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert mag == 1.0
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[-8:], plain[-8:] / 40, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    assert abs(M.mla_sm_scale(cfg) - 0.135234) < 1e-6
    ref_inv, ref_mag, ref_scale = C._yarn(dict(
        head_dim=128, rope_dim=64, rope_theta=10000,
        rope_yarn=[40, 4096, 32, 1, 1, 1]))
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_mag == 1.0 and abs(ref_scale - M.mla_sm_scale(cfg)) < 1e-9


def test_chunk_program_equals_single_steps(chunk):
    """The decode chunk over the latent pool == single steps of the same
    executable: tokens, logits, both page arrays and the router's loads,
    bit for bit, with lanes that die by their length cap, their EOS and at
    ``max_len``; a dead lane touches the trash block only and is left out
    of the loads."""
    scfg = C.serving_config(tiny())
    eng = ServingEngine(scfg, seed=3)
    nb = scfg.max_len // BS
    lanes = [lane(5, 30, 9), lane(7, 61, 2), lane(9, 100, 9),
             lane(2, scfg.max_len - 2, 9), lane(0, 0, 0)]
    tables = tables_for(lanes, nb, BS)
    rng = np.random.RandomState(5)
    names = dict(k=eng.pool.k_pages, v=eng.pool.v_pages,
                 wk=eng.window_pool.k_pages, wv=eng.window_pool.v_pages,
                 conv=eng.state.conv, ssm=eng.state.ssm)
    caches = {k: jnp.asarray(rng.randn(*a.shape), a.dtype)
              for k, a in names.items()}
    aux = ("wk", "wv", "conv", "ssm")
    none = np.zeros_like(tables)

    @jax.jit
    def step(tok, pos, ctx, left, eos, n, c):
        return M.decode_chunk(
            eng.params, tok, pos, tables, ctx, left, eos, n, c["k"], c["v"],
            scfg, chunk, dict({k: c[k] for k in aux}, wtables=none,
                              slots=np.zeros(len(lanes), np.int32)))

    def program(tok, pos, ctx, left, eos, n, c):
        rows, logits, kp, vp, _out, loads = step(tok, pos, ctx, left, eos,
                                                 np.int32(n), c)
        return rows, logits, dict(k=kp, v=vp), loads

    pools = {k: caches[k] for k in ("k", "v")}

    def run(lanes):
        return chunk_equals_single_steps(
            lambda *a: program(*a[:-1], dict(caches, **a[-1])),
            scfg.max_len, lanes, pools, chunk)

    rows, loads = run(lanes)
    lanes[2] = lane(9, 100, 9, eos=int(rows[min(1, chunk - 1), 2]))
    rows, loads = run(lanes)
    assert list((rows >= 0).sum(axis=0)[[0, 1, 3, 4]]) == [
        chunk, min(2, chunk), min(2, chunk), 0]
    # two expert layers, the router's count over all 16 experts: 4 a live
    # lane and layer
    assert loads.shape == (chunk, 2, 16)
    assert loads[0].sum(-1).tolist() == [16, 16]


# ------------------------------------------------------------- the engine
def test_counters_are_a_shares(served):
    """``routed_pairs`` is 4 a token and expert layer exactly; ``pairs`` the
    held experts' loads; the latent counters what the decode steps read."""
    cfg, params, _eng, _text, _want = served
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=3)
    rng = np.random.RandomState(8)
    prompts = [list(rng.randint(0, VOCAB, k)) for k in (5, 27, 40, 31)]
    n_new = [30, 11, 21, 6]
    routed0 = telemetry.counter("serving.moe.routed_pairs").value
    ctx0 = telemetry.counter("serving.latent.ctx_tokens").value
    eng.generate(prompts, n_new)
    st = eng.stats()
    moe_, lat = st["moe"], st["latent"]
    tokens = sum(len(p) for p in prompts) + sum(n - 1 for n in n_new)
    assert moe_["layer_tokens"] == 2 * tokens
    assert moe_["routed_pairs"] == 4 * moe_["layer_tokens"]
    assert moe_["experts_held"] == [0, 4] and moe_["num_experts"] == 16
    assert np.shape(moe_["tokens_per_expert"]) == (2, 4)
    assert moe_["pairs"] == int(np.sum(moe_["tokens_per_expert"]))
    assert 0 < moe_["pairs"] < moe_["routed_pairs"]
    assert lat["prefill_tokens"] == sum(len(p) for p in prompts)
    assert lat["lane_steps"] == sum(n - 1 for n in n_new)
    # a stream's j-th decode step reads its prompt, the j tokens before and
    # its own
    assert lat["ctx_tokens"] == sum(
        sum(len(p) + j + 1 for j in range(n - 1))
        for p, n in zip(prompts, n_new))
    assert telemetry.counter("serving.moe.routed_pairs").value - routed0 \
        == moe_["routed_pairs"]
    assert telemetry.counter("serving.latent.ctx_tokens").value - ctx0 \
        == lat["ctx_tokens"]
    assert st["kv_page_shape"] == [1, 128] and st["kv_head_major"]
    assert eng.pool.v_pages.shape[-1] == 128
    assert eng.pool.nbytes() == 3 * 65 * 16 * (128 + 128) * 4 \
        + eng.pool.extra_nbytes
    assert eng.pool.used() == 0


def test_a_dry_pool_preempts_the_youngest_and_replays_it():
    """Recompute preemption: the younger stream gives its blocks back, is
    replayed through prefill, and both streams' tokens are an unpressed
    engine's."""
    scfg = C.serving_config(tiny())
    prompts = [list(range(1, 30)), list(range(40, 69))]
    oracle = ServingEngine(scfg, seed=2).generate(prompts, 40)
    eng = ServingEngine(scfg, seed=2)
    hogged = eng.pool.alloc(eng.pool.available() - 7)
    reqs = [eng.submit(p, 40) for p in prompts]
    _drain(eng)
    assert [r.state for r in reqs] == [FINISHED] * 2
    assert reqs[1].preemptions >= 1 and reqs[0].preemptions == 0
    assert [list(r.generated) for r in reqs] == oracle
    assert eng.pool.used() == len(hogged)


def test_concurrent_is_sequential():
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, VOCAB, k)) for k in (5, 17, 40, 28)]
    scfg = C.serving_config(tiny())
    together = ServingEngine(scfg, seed=3).generate(prompts, 30)
    alone = ServingEngine(scfg, seed=3)
    assert together == [alone.generate([p], 30)[0] for p in prompts]


# ------------------------------------------------------- the wrong servers
@pytest.mark.parametrize("name", [
    "zeroed_expert", "no_shared", "no_group_limit", "no_renorm", "no_scale",
    "no_mscale", "unrotated_key"])
def test_a_wrong_server_is_far_from_the_reference(served, name):
    cfg, params, _eng, _text, _want = served
    seen = W.reading(cfg, C, params, name, 7)
    assert seen["prefill_quartile"] > 2e-3, seen
    assert seen["decode_quartile"] > 2e-3, seen
    assert not seen["correct_by_probe"]


@pytest.mark.parametrize("name", ["zeroed_expert", "float8_experts",
                                  "no_group_limit"])
def test_a_fault_of_the_held_experts_fails_the_held_pass(served, name):
    """What this rank's experts and the group limit get wrong is a small
    part of the answer as served and most of it in the held pass."""
    cfg, params, _eng, _text, _want = served
    seen = W.reading(cfg, C, params, name, 7)
    assert seen["held"]["quartile"] > 2 * seen["quartile"], seen
    assert seen["held"]["quartile"] > 2e-2, seen


@pytest.mark.parametrize("name", ["latent_before_norm",
                                  "latent_not_written"])
def test_a_fault_of_the_decode_path_alone_fails_the_probe(served, name):
    """The prefilled rows stay sound, the decoded rows do not, and
    ``quartile`` (what ``PROBE_RTOL`` bounds) is the worse half's."""
    cfg, params, _eng, _text, _want = served
    seen = W.reading(cfg, C, params, name, 7)
    assert seen["prefill_quartile"] < 1e-4 < 2e-3 < seen["decode_quartile"]
    assert seen["quartile"] == seen["decode_quartile"]


def test_the_sound_server_passes_where_the_wrong_ones_fail(served):
    cfg, params, _eng, _text, _want = served
    seen = W.reading(cfg, C, params, "sound", 7, gaps=True)
    assert seen["worst"] < 1e-4 and seen["gap_worst"] < 1e-3
    assert seen["correct_by_probe"] and seen["refused_by"] == []
    assert seen["held"]["worst"] < 1e-4
    # nothing stays planted behind a reading
    assert M.mla_sm_scale.__module__ == M.__name__
    assert moe.route.__module__ == moe.__name__


def test_the_harness_calls_a_wrong_server_not_correct():
    """``tools/wrong_servers.py --cell``: ``benchmark/run.py`` over the
    rehearsal cell with every decode step's latent thrown away. The
    comparison that refuses it is the driver's own, in the run's last line,
    by the decoded half alone; the probe alone then reads another seed over
    the same planted engine."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "wrong_servers.py"),
         "--cell", "dotsvlm1-tiny", "--rehearsal", "--faults",
         "latent_not_written", "--seeds", "3300000031", "--probe-seeds",
         "3300000032", "--seconds", "2"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    last = next(l for l in lines if "observed" in l)
    assert last["correct"] is False and last["failed"] == 0
    why = [l["problem"] for l in lines if l.get("bench") == "correct"]
    assert any("first quartile of the worse half" in p for p in why)
    ref = next(l for l in lines if l.get("bench") == "reference")["logits"]
    assert ref["prefill_quartile"] < ref["band"] < ref["decode_quartile"]
    through, alone = [l for l in lines if l.get("fault")]
    assert through["through"] == "benchmark/run.py"
    assert through["correct"] is False
    assert alone["seed"] == 3300000032 and "quartile" in alone["refused_by"]


# ----------------------------------------------------------- configuration
def test_serving_config_refuses_what_latents_cannot_do_yet():
    cfg = tiny()
    cfg["engine"]["prefix_cache"] = True
    with pytest.raises(ValueError, match="prefix_cache needs .*extend step "
                                         "over cached latents"):
        C.serving_config(cfg)
    cfg["engine"].update(prefix_cache=False, spec_k=2)
    with pytest.raises(ValueError, match="spec_k > 0 needs .*verify pass"):
        C.serving_config(cfg)
    cfg["engine"].update(prefix_cache=None, spec_k=0)
    scfg = C.serving_config(cfg)
    assert scfg.prefix_cache is False and scfg.latent and scfg.hybrid
    assert not scfg.stateful
    full = scfg.cache_specs().full
    assert (full.k_rows, full.v_rows, full.head_major) == (
        (1, 128), (1, 128), True)
    assert scfg.expert_layers == 2 and scfg.experts_here == (0, 4)
    model = {k: v for k, v in cfg["model"].items() if k != "vocab"}

    def bad(match, **changed):
        with pytest.raises(ValueError, match=match):
            ServingConfig(**dict(model, vocab_size=VOCAB, **changed))

    bad("no other attention kind", layer_kinds=["mla", "full", "mla"],
        num_kv_heads=2)
    bad("need pos='rope'", pos="none")
    bad("is no part of 16 experts", experts_held=[14, 4])
    bad("must leave a layer with experts", first_dense=3)
    bad("do not make 4 groups", topk_group=5)
    bad("router must be", router="argmax")
    with pytest.raises(ValueError, match="belong to a model with "
                                         "layer_kinds"):
        M.ModelConfig(tie_embed=True)
    # ... and since PR 40 a one-block model may set its norms' epsilon
    assert M.ModelConfig(norm_eps=1e-6).key()[-4:] == (False, 1e-6, 1, False)
    with pytest.raises(ValueError, match="only 'mla' has rotary"):
        M.ModelConfig(97, 1, 64, 4, 128, 64, layer_kinds=["mamba"],
                      pos="rope")


def test_param_shapes_are_a_shares():
    scfg = C.serving_config(tiny())
    shapes = M.param_shapes(scfg)
    assert shapes["layer0_ffn1_weight"] == (2 * 96, 64)
    assert "layer0_router_weight" not in shapes
    assert shapes["layer1_router_weight"] == (16, 64)
    assert shapes["layer1_router_bias"] == (16,)
    assert shapes["layer1_experts_gate_weight"] == (4, 32, 64)
    assert shapes["layer2_shared_down_weight"] == (64, 32)
    assert shapes["layer1_mla_kv_down_weight"] == (128 + 8, 64)
    assert shapes["layer1_mla_q_up_weight"] == (8 * (16 + 8), 48)
    assert shapes["layer1_mla_kv_up_weight"] == (8 * (16 + 16), 128)
    assert "layer1_attn_in_weight" not in shapes
    params = M.random_params(scfg, seed=1)
    assert np.abs(params["layer1_router_bias"]).max() > 0
    # OLMoE's block is what it was: every expert held, no bias, no shared
    olmoe = M.ModelConfig(97, 2, 64, 4, 32, 64, norm="rms", pos="rope",
                          qk_norm=True, num_experts=8, experts_per_tok=2,
                          bias=False)
    assert olmoe.expert_layers == 2 and olmoe.experts_here == (0, 8)
    assert M.param_shapes(olmoe)["layer0_experts_gate_weight"] == (8, 32, 64)
    assert len(olmoe.key()) == 14
