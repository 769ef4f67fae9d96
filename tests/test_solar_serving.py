"""Solar-Open2's kinds through ``ServingEngine`` at a tiny size (CPU, float32,
seeded random weights): two periods of ``[gqa, kda, kda, kda]`` — gated,
position-free grouped-query attention (4 query heads of 16 over 2 K/V heads)
beside gated delta-rule linear layers (4 heads of 16 keys and 16 values, a
conv of 4 taps, gates of rank 16, ``beta`` doubled), a matrix state a head in
the streams' slots beside the full pool — over 4 of 16 sigmoid-routed experts
held, 2 a token, and a shared expert. Held against the configuration module's
plain reference (``benchmark/configs/solar-open2-ep16-bf16.py``: the linear
layer a token at a time, nothing of ``ops/`` or ``serving/``), logits to 1e-4.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import kda, moe
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving import model as M
from mxnet_tpu.serving.scheduler import FINISHED

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from tools import wrong_servers as W  # noqa: E402

_CFG, C = W.load_config(os.path.join(
    ROOT, "benchmark", "rehearsal", "configs", "solar-tiny.json"))
VOCAB, BS, CH = 211, 16, kda.CHUNK


def tiny(dtype="float32", **model):
    """A configuration file's worth of the tiny model."""
    cfg = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in _CFG.items()}
    cfg["model"].update(vocab=VOCAB, **model)
    cfg["engine"]["kv_dtype"] = cfg["weights_dtype"] = dtype
    # the rehearsal file draws N(0, 0.02) as the published widths' file does
    # (its bfloat16 probe reads 2% there); in float32 a larger draw makes a
    # fault weigh whole logits, not hundredths
    cfg["init"].update(std=0.113, gate_gain=2.0)
    return cfg


@pytest.fixture(scope="module")
def served():
    """(cfg, params, engine, a 400-token text, the reference's logits)"""
    cfg = tiny()
    params = C.init_params(cfg, 3)
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=3)
    text = np.random.RandomState(0).randint(0, VOCAB, 400).astype(np.int32)
    return cfg, params, eng, text, C.reference_logits(cfg)(params, text)


def period(**model):
    """One period of the pattern, and a probe of three prefixes (the longest
    two chunks of the chunkwise form) and four lanes: what the engine's
    book-keeping and the wrong servers need, at half the programs."""
    cfg = tiny(num_layers=4, layer_kinds=["full", "kda", "kda", "kda"],
               **model)
    cfg["reference"].update(probe_len=200, probe_prefixes=[12, 3],
                            probe_decode=[8, 24])
    return cfg


@pytest.fixture(scope="module")
def one_period():
    cfg = period()
    return cfg, C.init_params(cfg, 3)


def _drain(eng):
    while eng.has_work():
        eng.step()


# ------------------------------------------------------- against the oracle
@pytest.mark.parametrize("n", [1, 15, 16, 17, CH - 1, CH, CH + 1,
                               3 * CH + 5])
def test_prefill_logits_are_the_references(served, n):
    """Lengths that are no whole chunk of the chunkwise form (128 rows) and
    no whole block (16): one row, a chunk less one, a chunk, a chunk and
    one, three chunks and five."""
    _cfg, _params, eng, text, want = served
    np.testing.assert_allclose(eng.prefill_logits(text[:n]), want[n - 1],
                               atol=1e-4)


@pytest.mark.parametrize("n,start", [(12, 1), (40, 7), (140, 120),
                                     (200, CH - 1), (300, CH + 1),
                                     (400, 3 * CH + 5), (33, 31)])
def test_prefill_then_decode_through_slots_and_pool(served, n, start):
    """A prefill of ``start`` tokens, then forced decode steps up to ``n``:
    the prompt's last state and conv tail handed from the chunkwise kernel's
    slot to the decode update's, across a chunk's edge and block edges, the
    full pool written beside the slots."""
    _cfg, _params, eng, text, want = served
    got = eng.prefill_logits(text[:n], decode_from=start)
    np.testing.assert_allclose(got, want[n - 1], atol=1e-4)


def test_lanes_decoded_together_are_lanes_decoded_alone(served):
    _cfg, _params, eng, text, want = served
    cuts = [(40, 7), (150, 120), (33, 31), (300, 129)]
    got = eng.decode_logits([text[:n] for n, _ in cuts],
                            [s for _, s in cuts])
    for row, (n, _s) in zip(got, cuts):
        np.testing.assert_allclose(row, want[n - 1], atol=1e-4)


@pytest.mark.parametrize("fault", C.FAULTS)
def test_a_misread_equation_is_far_from_the_engine(served, fault):
    """The engine is the SOUND reference's to 1e-4; each misreading of the
    equations (the decay left out, ``beta`` not doubled, q and k not
    normalised, either output gate left out), planted in a COPY of the
    reference, lies a thousand times further from the engine."""
    cfg, params, eng, text, want = served
    wrong = C.reference_logits(cfg, [fault])(params, text)
    got = np.stack([eng.prefill_logits(text[:n]) for n in (40, 390)])
    sound = np.abs(got - want[[39, 389]]).max()
    planted = np.abs(got - wrong[[39, 389]]).max()
    assert sound < 1e-4 < 1e-1 < planted


def test_the_gates_leave_their_rest_values(served):
    """What the initialisation is set by: the decay spans forgetting in a
    few tokens to hardly at all, ``beta`` and both gates leave 1 and 1/2 by
    tenths."""
    cfg, params, _eng, text, _want = served
    spread = C.gate_spread(cfg)(params, text[:200])
    assert spread["alpha"][0] < 0.6 and spread["alpha"][2] > 0.99
    for name, rest in (("beta", 1.0), ("linear_gate", 0.5),
                       ("gqa_gate", 0.5)):
        lo, _mid, hi = spread[name]
        assert lo < rest - 0.1 and hi > rest + 0.1, (name, spread[name])


def test_generated_tokens_through_chunks_are_the_references(served):
    cfg, params, eng, text, _want = served
    prompt = [int(t) for t in text[:21]]
    tokens = eng.generate([prompt], 40)[0]
    off, matches = C.make_reference(cfg)(params, prompt, tokens)
    assert off == [] and matches == 40


def test_the_cells_probe_runs_over_the_engine(served):
    cfg, params, eng, _text, _want = served
    seen = C.make_probe(cfg)(params, eng, 5)
    assert seen["rows"] == 10 and seen["held"]["rows"] == 10
    assert seen["worst"] < 1e-4 and seen["held"]["worst"] < 1e-4
    # data only: the weights are what they were
    again = C.make_probe(cfg)(params, eng, 5)
    assert again["worst"] == seen["worst"]


# ------------------------------------------------- the two lowerings, alone
def _rows(seq, heads, dk, dv, seed=0):
    r = np.random.RandomState(seed)
    q = r.randn(seq, heads, dk)
    k = r.randn(seq, heads, dk)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(r.uniform(np.log(1e-3), np.log(3.0), (seq, heads, dk)))
    g[:, 0, 0] = -6.0       # a channel that forgets all in a few tokens
    beta = 2 / (1 + np.exp(-r.randn(seq, heads)))
    return [jnp.asarray(t, jnp.float32)
            for t in (q, k, r.randn(seq, heads, dv), g, beta)]


@pytest.mark.parametrize("seq,length,chunk", [
    (40, 37, 16), (64, 64, 16), (48, 1, 16), (80, 53, 16), (24, 24, 64)])
def test_the_chunkwise_form_is_the_recurrence(seq, length, chunk):
    """``kda_chunk``'s XLA lowering against the token-by-token scan, outputs
    AND the state handed on, at decays down to e^-3 a step (a chunk's
    cumulative decay underflows float32: no exponent is taken alone); rows at or
    past ``length`` leave the state alone; nothing but the stream's slot is
    written."""
    q, k, v, g, beta = _rows(seq, 2, 16, 24, seed=seq)
    slots = jnp.asarray(np.random.RandomState(1).randn(2, 3, 2, 16, 24),
                        jnp.float32)
    want_o, want_s = kda.kda_recurrence(q, k, v, g, beta,
                                        jnp.zeros((2, 16, 24)), length)
    o, out = kda.kda_chunk_reference(q, k, v, g, beta, length, slots, 2, 1,
                                     chunk=chunk)
    np.testing.assert_allclose(o[:length], want_o[:length], atol=2e-6)
    np.testing.assert_allclose(out[1, 2], want_s, atol=2e-6)
    np.testing.assert_array_equal(out[0], slots[0])
    np.testing.assert_array_equal(out[1, :2], slots[1, :2])
    if length >= 16:    # 1 / Gamma would be infinite
        assert float(jnp.exp(jnp.cumsum(g[:16], 0)).min()) < 1e-38


@pytest.mark.parametrize("seq,length", [(256, 200), (128, 128), (130, 1),
                                        (384, 129)])
def test_the_chunk_kernel_is_its_xla_lowering(seq, length):
    """The Pallas kernel in interpret mode (the pairs' scores through the
    group edges, the triangular system by halves) against the XLA lowering
    and the recurrence; a chunk wholly past ``length`` is skipped and its
    rows of ``o`` are zeros."""
    q, k, v, g, beta = _rows(seq, 2, 128, 128, seed=seq)
    slots = jnp.zeros((2, 3, 2, 128, 128), jnp.float32)
    want_o, want_s = kda.kda_recurrence(q, k, v, g, beta,
                                        jnp.zeros((2, 128, 128)), length)
    ref_o, ref = kda.kda_chunk_reference(q, k, v, g, beta, length, slots, 2,
                                         1)
    o, out = kda._chunk_pallas(q, k, v, g, beta, length, slots,
                               jnp.int32(2), 1, interpret=True)
    np.testing.assert_allclose(o[:length], ref_o[:length], atol=5e-6)
    np.testing.assert_allclose(o[:length], want_o[:length], atol=5e-6)
    np.testing.assert_allclose(out[1, 2], want_s, atol=2e-5)
    np.testing.assert_allclose(out[1, 2], ref[1, 2], atol=2e-5)
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert not np.asarray(o[-(-length // kda.CHUNK) * kda.CHUNK:],
                          np.float32).any()


def test_the_step_kernel_is_its_xla_lowering():
    """The decode kernel in interpret mode: three streams' states read from
    and written to their slots, two padded rows on the trash slot, the
    other slots and the other layer untouched."""
    q, k, v, g, beta = _rows(5, 16, 128, 128, seed=7)
    state = jnp.asarray(np.random.RandomState(1).randn(2, 6, 16, 128, 128),
                        jnp.float32)
    slots = jnp.asarray([3, 1, 4, 0, 0])
    want_o, want = kda.kda_step_reference(q, k, v, g, beta, state, slots, 1)
    o, out = kda._step_pallas(q, k, v, g, beta, state, slots, 1,
                              interpret=True)
    np.testing.assert_allclose(o[:3], want_o[:3], atol=2e-6)
    np.testing.assert_allclose(out[1, 1:], want[1, 1:], atol=2e-6)
    np.testing.assert_array_equal(out[0], state[0])
    np.testing.assert_array_equal(out[1, [2, 5]], state[1, [2, 5]])
    with pytest.raises(ValueError, match="kept in float32"):
        kda.kda_step(q, k, v, g, beta, state.astype(jnp.bfloat16), slots, 1)


# ---------------------------------------------------------------- the share
def _layer_params(cfg, seed):
    params = C.init_params(cfg, seed)
    return {k[len("layer1"):]: v for k, v in params.items()
            if k.startswith("layer1_")}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """An expert layer computed by four ranks of four experts each, the
    shared expert counted ONCE, is the uncut layer: in the program
    (``moe_ffn(held=...)`` plus ``_gated``) and in the reference; and a
    rank's routed part is the reference's part of that rank."""
    cfg = tiny(experts_held=None)
    m = cfg["model"]
    p = _layer_params(cfg, 6)
    h = jnp.asarray(np.random.RandomState(6).randn(24, 64), jnp.float32)
    how = dict(kind="sigmoid_group", bias=p["_router_bias"], n_group=1,
               topk_group=1, scale=1.0)
    stacks = [p["_experts_%s_weight" % n] for n in ("gate", "up", "down")]
    hi = jax.lax.Precision.HIGHEST
    shared = M._gated(h, *(p["_shared_%s_weight" % n]
                           for n in ("gate", "up", "down")), hi)
    uncut, load = moe.moe_ffn(h, p["_router_weight"], *stacks, 2, **how)
    assert int(load.sum()) == 2 * 24

    def ref_layer(params, held=None, shared=True):
        return np.asarray(C._experts(
            h, {"layer1" + k: v for k, v in params.items()}, "layer1", m,
            held, shared))

    with jax.default_matmul_precision("highest"):
        whole = ref_layer(p)
        parts, ref_parts = [], []
        for rank in range(4):
            held = (4 * rank, 4)
            mine = [s[held[0]:held[0] + 4] for s in stacks]
            part, rank_load = moe.moe_ffn(h, p["_router_weight"], *mine, 2,
                                          held=held, **how)
            np.testing.assert_array_equal(np.asarray(rank_load),
                                          np.asarray(load))
            parts.append(np.asarray(part))
            ref_parts.append(ref_layer(
                dict(p, **{"_experts_%s_weight" % n: w for n, w in zip(
                    ("gate", "up", "down"), mine)}), held, shared=False))
            np.testing.assert_allclose(parts[-1], ref_parts[-1], atol=2e-5)
        only_shared = whole - ref_layer(p, shared=False)
    np.testing.assert_allclose(sum(parts), np.asarray(uncut), atol=2e-5)
    np.testing.assert_allclose(only_shared, np.asarray(shared), atol=2e-5)
    np.testing.assert_allclose(sum(ref_parts) + only_shared, whole,
                               atol=2e-5)
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), whole,
                               atol=5e-5)
    assert np.abs(whole).max() > 10 * 5e-5
    assert np.abs(only_shared).max() > 10 * 5e-5


def test_counters_are_a_shares_and_the_states(served):
    """``stats()``: the router's choices are 2 a live token and layer, the
    pairs computed are the held experts' loads; ``linear`` has a state
    update a live lane, decode step and "kda" layer, the rows and chunks
    the prefill kernel took, the slots in use; ``hybrid`` the keys one GQA
    layer's walk read; the loop's records carry lane-steps, prompt tokens
    and rungs."""
    cfg, params, _eng, text, _want = served
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=3)
    spans = ((0, 5), (5, 45), (50, 250))
    reqs = [eng.submit([int(t) for t in text[a:b]], 30) for a, b in spans]
    eng.step()
    mid = eng.stats()
    assert mid["linear"]["slots_used"] == 3
    assert mid["linear"]["slot_bytes"] == 6 * (4 * 16 * 16 * 4
                                               + 3 * 3 * 4 * 16 * 4)
    assert mid["state"]["slots"] == 4 and mid["state"]["slots_used"] == 3
    _drain(eng)
    stats = eng.stats()
    moe_, lin, hyb = stats["moe"], stats["linear"], stats["hybrid"]
    assert moe_["routed_pairs"] == 2 * moe_["layer_tokens"]
    assert moe_["pairs"] == sum(map(sum, moe_["tokens_per_expert"]))
    assert 0 < moe_["pairs"] < moe_["routed_pairs"]
    steps = sum(len(r.generated) - 1 for r in reqs)
    assert lin["layers"] == 6 and lin["state_updates"] == 6 * steps
    assert lin["prefill_tokens"] == 6 * (5 + 40 + 200)
    assert lin["prefill_chunks"] == 6 * (1 + 1 + 2)
    assert lin["slots_used"] == 0
    ctxs = [b - a + j for r, (a, b) in zip(reqs, spans)
            for j in range(1, len(r.generated))]
    assert hyb["lane_steps"] == steps
    assert hyb["full_ctx_tokens"] == sum(ctxs)
    assert hyb["window_ctx_tokens"] == 0
    recs = list(eng.obs._ring)
    assert sum(r.lane_steps for r in recs) == steps
    assert sum(r.full_ctx_tokens for r in recs) == hyb["full_ctx_tokens"]
    assert sum(r.prefill_tokens for r in recs) == 245
    assert sum(r.prefill_rows for r in recs) == 16 + 64 + 256
    assert stats["state"]["full_pool_readers"] == 2


# ------------------------------------------------------ slots beside a pool
def test_slots_and_blocks_are_booked_and_freed_together():
    """Admission books a slot and the pool's blocks or neither; slots, not
    blocks, bound admission (four streams of a pool that holds more); a
    freed slot is the next stream's."""
    from mxnet_tpu.serving.kv_cache import KVCacheOOM

    scfg = C.serving_config(period())
    eng = ServingEngine(scfg, seed=2)
    assert eng.pool.spec.k_rows == eng.pool.spec.v_rows == (2, 128)
    assert eng.pool.spec.head_major and eng.streams.pool is None
    assert eng.state.ssm.shape == (3, 5, 4, 16, 16)
    assert eng.state.ssm.dtype == jnp.float32
    assert eng.state.conv.shape == (3, 5, 3 * 3 * 4 * 16)
    hog = [eng.state.alloc() for _ in range(4)]
    req = eng.submit(list(range(1, 20)), 4)
    with pytest.raises(KVCacheOOM):
        eng.streams.admit(req, 19)
    assert req.slot is None
    for s in hog:
        eng.state.free(s)
    reqs = [eng.submit(list(range(1 + i, 12 + i)), 20) for i in range(6)]
    most = 0
    while eng.has_work():
        eng.step()
        most = max(most, eng.state.used())
    assert most == 4 and all(r.state == FINISHED for r in [req] + reqs)
    assert eng.pool.used() == eng.state.used() == 0


def test_a_dry_pool_preempts_the_youngest_and_replays_it():
    """Recompute preemption releases the slot with the blocks; the replayed
    stream (prompt plus generated tokens prefilled again, its state made
    anew by the chunkwise kernel) gives an unpressed engine's tokens."""
    scfg = C.serving_config(period())
    prompts = [list(range(1, 30)), list(range(40, 69))]
    oracle = ServingEngine(scfg, seed=2).generate(prompts, 40)
    eng = ServingEngine(scfg, seed=2)
    hogged = eng.pool.alloc(eng.pool.available() - 7)
    reqs = [eng.submit(p, 40) for p in prompts]
    _drain(eng)
    assert [r.state for r in reqs] == [FINISHED] * 2
    assert reqs[1].preemptions >= 1 and reqs[0].preemptions == 0
    assert [list(r.generated) for r in reqs] == oracle
    assert eng.pool.used() == len(hogged) and eng.state.used() == 0


def test_streams_that_swap_slots_do_not_see_each_others_state():
    """Two streams run one after the other through the SAME slot, and side
    by side with their slots the other way round: a stream's tokens are
    its own whatever the slot held before (a prefill starts from an empty
    state; nothing is cleared on release)."""
    scfg = C.serving_config(period())
    a, b = list(range(3, 40)), list(range(90, 110))
    alone = [ServingEngine(scfg, seed=2).generate([a], 30)[0]]
    eng = ServingEngine(scfg, seed=2)
    alone.append(eng.generate([b], 30)[0])      # b in a fresh engine
    first = eng.submit(a, 30)
    _drain(eng)
    slot = eng.state._free[-1]
    second = eng.submit(b, 30)
    eng.step()
    assert second.slot == slot          # the slot `a` left its state in
    _drain(eng)
    assert [list(first.generated), list(second.generated)] == alone
    # side by side, b admitted first: the slots the other way round
    both = ServingEngine(scfg, seed=2)
    rb, ra = both.submit(b, 30), both.submit(a, 30)
    _drain(both)
    assert [list(ra.generated), list(rb.generated)] == alone


def test_concurrent_is_sequential():
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, VOCAB, k)) for k in (5, 17, 140, 28)]
    scfg = C.serving_config(period())
    together = ServingEngine(scfg, seed=3).generate(prompts, 30)
    alone = ServingEngine(scfg, seed=3)
    assert together == [alone.generate([p], 30)[0] for p in prompts]


# ------------------------------------------------------------ configuration
def test_serving_config_refuses_what_a_matrix_state_cannot_do_yet():
    cfg = tiny()
    cfg["engine"]["prefix_cache"] = True
    with pytest.raises(ValueError, match="prefix_cache needs .*a snapshot "
                                         "of every linear layer's matrix "
                                         "state .*at a block boundary"):
        C.serving_config(cfg)
    cfg["engine"].update(prefix_cache=False, spec_k=2)
    with pytest.raises(ValueError, match="spec_k > 0 needs .*a verify pass "
                                         "over state"):
        C.serving_config(cfg)
    cfg["engine"].update(prefix_cache=None, spec_k=0)
    scfg = C.serving_config(cfg)
    assert scfg.prefix_cache is False and scfg.gqa and scfg.hybrid
    assert scfg.stateful and scfg.linear and not scfg.latent
    full, window = scfg.cache_specs()
    assert (full.k_rows, full.v_rows) == ((2, 128), (2, 128))
    assert full.layers == 2 and window.layers == 1          # a stand-in
    assert scfg.slot_shapes() == (3 * 3 * 4 * 16, (4, 16, 16))
    assert scfg.expert_layers == 8 and scfg.experts_here == (0, 4)
    model = {k: v for k, v in cfg["model"].items() if k != "vocab"}

    def bad(match, **changed):
        with pytest.raises(ValueError, match=match):
            ServingConfig(**dict(model, vocab_size=VOCAB, **changed))

    bad("takes 'swa', 'full' and 'kda' layers alone",
        layer_kinds=["full", "kda", "kda", "mamba"] * 2)
    bad("belong to a model whose attn_form is 'gqa'", attn_form="diff")
    bad("'kda' layers need kda_conv >= 2", kda_conv=1)
    bad("pos 'rope' or 'none'", pos="learned")
    with pytest.raises(ValueError, match="attn_gate .*belong to a model "
                                         "with layer_kinds"):
        M.ModelConfig(attn_gate=True)


def test_the_earlier_models_keys_are_what_they_were():
    """The linear layer's fields and the output gate stand behind the
    forty-three of a "gqa" model, and only where the model has "kda" layers
    or a gate: MiMo-V2.5's key is the forty-three it was, Phi-4-mini-flash's
    and dots.vlm1's the thirty-eight, a one-block model's the fourteen."""
    import json

    def key_of(name):
        cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                          name + ".json")))
        return ServingConfig.from_json(cfg).key()

    for name in ("phi4-mini-flash-bf16", "dots-vlm1-ep16-bf16"):
        assert len(key_of(name)) == 38
    assert len(key_of("gpt2-medium-fp32")) == 14
    assert len(key_of("olmoe-1b-7b-bf16")) == 14
    mimo = key_of("mimo-v2.5-ep16-bf16")
    assert len(mimo) == 43 and mimo[38:] == ("gqa", 8, 1e4, True, 0.707)
    mine = key_of("solar-open2-ep16-bf16")
    assert len(mine) == 48
    assert mine[38:] == ("gqa", 8, 1e4, False, 1.0, True, 64, 128, 4, True)


def test_param_shapes_are_the_cuts():
    """The published widths, recounted from ``param_shapes``: the table of
    PERF.md section 4."""
    import json

    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "solar-open2-ep16-bf16.json")))
    scfg = ServingConfig.from_json(cfg)
    shapes = M.param_shapes(scfg)

    def count(*parts):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if any(p in k for p in parts))

    assert shapes["layer1_kda_in_weight"] == (3 * 8192, 4096)
    assert shapes["layer1_kda_conv_weight"] == (4, 3 * 8192)
    assert shapes["layer0_attn_in_weight"] == (8192 + 2 * 1024, 4096)
    assert shapes["layer0_attn_gate_weight"] == (8192, 4096)
    assert shapes["layer0_router_weight"] == (320, 4096)
    assert shapes["layer0_experts_up_weight"] == (20, 1280, 4096)
    assert shapes["layer0_shared_up_weight"] == (1280, 4096)
    assert count("layer1_kda_") == 137740480
    assert count("layer0_attn_") == 109051904
    assert count("layer0_shared_", "layer0_router_", "layer0_ln") == 17047872
    assert count("layer1_") == 469361152                # a linear layer
    assert count("layer0_") == 440672576                # a GQA layer
    assert count("embed_weight", "lm_head_weight") == 201326592
    assert sum(int(np.prod(s)) for s in shapes.values()) == 3898842752
    assert scfg.slot_shapes() == (3 * 24576, (64, 128, 128))
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    kinds = cfg["model"]["layer_kinds"]
    assert [i for i in cfg["layers_served"] if kinds[i] == "full"] == [
        i for i in cfg["gqa_layers"] if i < len(kinds)]


# ------------------------------------------------------- the wrong servers
def test_the_sound_server_passes_where_the_wrong_ones_fail(one_period):
    cfg, params = one_period
    sound = W.reading(cfg, C, params, "sound", 5)
    assert sound["worst"] < 1e-4 and sound["held"]["worst"] < 1e-4


@pytest.mark.parametrize("name", [
    "no_decay", "beta_not_doubled", "qk_not_normalised",
    "conv_tail_not_carried", "chunk_state_not_handed", "state_not_written",
    "no_linear_gate", "no_gqa_gate", "bf16_state", "float8_all"])
def test_a_wrong_server_is_far_from_the_reference(one_period, name):
    """``tools/wrong_servers.py``'s faults of this model, each planted in an
    engine of its own: the probe reads them a hundred times further from
    the reference than the sound engine (the case above) — the faults of
    the decode path and of the hand-over in the decoded half alone, a lost
    hand-over between chunks in the prefixes of several chunks."""
    cfg, params = one_period
    out = W.reading(cfg, C, params, name, 5)
    # bfloat16's eight bits after each of 8-24 updates: thousandths
    far = 1e-3 if name == "bf16_state" else 1e-2
    assert max(out["quartile"], out["third_quartile"]) > far, out
    if name in ("conv_tail_not_carried", "state_not_written", "bf16_state"):
        assert out["prefill_quartile"] < 1e-4 < far < out["decode_quartile"]
    assert M.kda_step is kda.kda_step               # the patches are undone
    assert M.kda_chunk is kda.kda_chunk
    assert M._kda_heads.__module__ == M.__name__


@pytest.mark.slow
def test_the_harness_calls_a_wrong_server_not_correct():
    """``tools/wrong_servers.py --cell``: ``benchmark/run.py`` over the
    rehearsal cell with every decode step's states thrown away. The
    comparison that refuses it is the driver's own, in the run's last
    line."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "wrong_servers.py"),
         "--cell", "solar-tiny", "--rehearsal", "--faults",
         "state_not_written", "--seeds", "3300000041", "--seconds", "2"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    last = next(l for l in lines if "observed" in l)
    assert last["correct"] is False and last["failed"] == 0


def test_tools_serve_builds_the_engine_from_the_configuration_file():
    """``tools/serve.py --model-config``: the rehearsal file's ``model`` and
    ``engine`` objects, seeded weights in the file's type, no side script."""
    import types

    from tools import serve

    eng = serve.build_engine(types.SimpleNamespace(
        model_config=os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                                  "solar-tiny.json"),
        checkpoint=None, seed=3))
    assert eng.config.linear and eng.params["embed_weight"].dtype \
        == jnp.bfloat16
    out = eng.generate([[1, 2, 3, 4, 5]], 6)
    assert len(out[0]) == 6
    assert eng.stats()["linear"]["state_updates"] == 6 * 5
