"""MiMo-V2.5's kinds through ``ServingEngine`` at a tiny size (CPU, float32,
seeded random weights): window and full layers of PLAIN grouped-query
attention in the pattern full, 5 x window, full — 16 query heads over 4 K/V
heads in a full layer and 8 in a window layer, a head of 48 = 16 rotary + 32
plain lanes, values of 32, a window of 8 keys with a learned sink, a value
scale — over two pools of unequal rows, behind a leading dense layer with 4
of 16 sigmoid-routed experts held, 2 a token. Held against the
configuration module's plain reference (``benchmark/configs/
mimo-v2.5-ep16-bf16.py``: nothing of ``ops/`` or ``serving/``), logits to
1e-4.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import moe
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving import model as M
from mxnet_tpu.serving.scheduler import FINISHED

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from tools import wrong_servers as W  # noqa: E402

_CFG, C = W.load_config(os.path.join(
    ROOT, "benchmark", "rehearsal", "configs", "mimo-tiny.json"))
VOCAB, BS, WINDOW = 211, 16, 8


def tiny(dtype="float32", **model):
    """A configuration file's worth of the tiny model."""
    cfg = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in _CFG.items()}
    cfg["model"].update(vocab=VOCAB, **model)
    cfg["engine"]["kv_dtype"] = cfg["weights_dtype"] = dtype
    return cfg


@pytest.fixture(scope="module")
def served():
    """(cfg, params, engine, a 150-token text, the reference's logits)"""
    cfg = tiny()
    params = C.init_params(cfg, 3)
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=3)
    text = np.random.RandomState(0).randint(0, VOCAB, 150).astype(np.int32)
    return cfg, params, eng, text, C.reference_logits(cfg)(params, text)


def _drain(eng):
    while eng.has_work():
        eng.step()


# ------------------------------------------------------- against the oracle
@pytest.mark.parametrize("n", [5, 8, 9, 16, 17, 40, 100, 150])
def test_prefill_logits_are_the_references(served, n):
    """Below, at and past the window's edge (8) and a block's (16)."""
    _cfg, _params, eng, text, want = served
    np.testing.assert_allclose(eng.prefill_logits(text[:n]), want[n - 1],
                               atol=1e-4)


@pytest.mark.parametrize("n,start", [(12, 3), (40, 7), (40, 20), (100, 15),
                                     (150, 10), (150, 120), (33, 31)])
def test_prefill_then_decode_through_both_pools(served, n, start):
    """A prefill of ``start`` tokens, then forced decode steps up to ``n``:
    across the window's edge (a stream shorter than the window grows past
    it), across block edges (a window block freed every 16 tokens, five
    layers at once), the full pool written beside the window pool."""
    _cfg, _params, eng, text, want = served
    freed = eng.streams.blocks_freed
    got = eng.prefill_logits(text[:n], decode_from=start)
    np.testing.assert_allclose(got, want[n - 1], atol=1e-4)
    if n - start > 2 * BS:
        assert eng.streams.blocks_freed > freed
    assert eng.pool.used() == 0 and eng.window_pool.used() == 0


def test_lanes_decoded_together_are_lanes_decoded_alone(served):
    """Ragged contexts side by side in one bucket, each lane's window and
    blocks its own."""
    _cfg, _params, eng, text, want = served
    cuts = [(150, 100), (30, 5), (64, 40), (17, 9)]
    got = eng.decode_logits([text[:n] for n, _ in cuts],
                            [s for _, s in cuts])
    for row, (n, _s) in zip(got, cuts):
        np.testing.assert_allclose(row, want[n - 1], atol=1e-4)


@pytest.mark.parametrize("fault", C.FAULTS)
def test_a_misread_equation_is_far_from_the_engine(served, fault):
    """float32 is tight enough that each way to misread the layer — a
    window one key too long, the sink left out, the two rotary bases
    swapped, rotary on all lanes of a head, the value scale left out —
    planted in a COPY of the reference, lies a thousand times further from
    the engine than the sound reference does."""
    cfg, params, eng, text, want = served
    wrong = C.reference_logits(cfg, faults=(fault,))(params, text)
    got = np.stack([eng.prefill_logits(text[:n]) for n in (40, 150)])
    sound = np.abs(got - want[[39, 149]]).max()
    planted = np.abs(got - wrong[[39, 149]]).max()
    assert sound < 1e-4 < 1e-1 < planted


def test_the_sink_takes_a_real_share_of_a_windows_softmax(served):
    """The initialisation's departure: sinks drawn N(2, 0.5) take a tenth
    to a half of a window's mass, so that their loss cannot hide."""
    cfg, params, _eng, text, _want = served
    assert 0.1 < C.sink_mass(cfg)(params, text[:64]) < 0.6


def test_generated_tokens_through_chunks_are_the_references(served):
    cfg, params, eng, text, _want = served
    prompt = [int(t) for t in text[:21]]
    tokens = eng.generate([prompt], 40)[0]
    off, matches = C.make_reference(cfg)(params, prompt, tokens)
    assert off == [] and matches == 40


def test_the_cells_probe_runs_over_the_engine(served):
    cfg, params, eng, _text, _want = served
    seen = C.make_probe(cfg)(params, eng, 5)
    assert seen["rows"] == 8 and seen["held"]["rows"] == 8
    assert seen["worst"] < 1e-4 and seen["held"]["worst"] < 1e-4
    # data only: the weights are what they were
    again = C.make_probe(cfg)(params, eng, 5)
    assert again["worst"] == seen["worst"]


def test_bfloat16_serving_stays_near_the_reference():
    cfg = tiny("bfloat16")
    params = C.init_params(cfg, 4)
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=4)
    seen = C.make_probe(cfg)(params, eng, 6)
    assert seen["quartile"] < 0.05 and seen["held"]["quartile"] < 0.08


# ---------------------------------------------------------------- the share
def _layer_params(cfg, seed):
    params = C.init_params(cfg, seed)
    return {k[len("layer1"):]: v for k, v in params.items()
            if k.startswith("layer1_")}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """An expert layer computed by four ranks of four experts each is the
    uncut layer (no shared expert): in the program (``moe_ffn(held=...)``)
    and in the reference; and a rank's part is the reference's part of that
    rank."""
    cfg = tiny(experts_held=None)
    m = cfg["model"]
    p = _layer_params(cfg, 6)
    h = jnp.asarray(np.random.RandomState(6).randn(24, 64), jnp.float32)
    how = dict(kind="sigmoid_group", bias=p["_router_bias"], n_group=1,
               topk_group=1, scale=1.0)
    stacks = [p["_experts_%s_weight" % n] for n in ("gate", "up", "down")]
    uncut, load = moe.moe_ffn(h, p["_router_weight"], *stacks, 2, **how)
    assert int(load.sum()) == 2 * 24

    def ref_layer(m, params, held=None):
        return np.asarray(C._experts(
            h, {"layer1" + k: v for k, v in params.items()}, "layer1", m,
            held))

    with jax.default_matmul_precision("highest"):
        whole = ref_layer(m, p)
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
                m, dict(p, **{"_experts_%s_weight" % n: w for n, w in zip(
                    ("gate", "up", "down"), mine)}), held))
            np.testing.assert_allclose(parts[-1], ref_parts[-1], atol=2e-5)
    np.testing.assert_allclose(sum(parts), np.asarray(uncut), atol=2e-5)
    np.testing.assert_allclose(sum(ref_parts), whole, atol=2e-5)
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    assert np.abs(whole).max() > 10 * 5e-5


def test_counters_are_a_shares_and_the_walks(served):
    """``stats()``: the router's choices are 2 a live token and expert
    layer, the pairs computed are the held experts' loads; ``hybrid`` has
    the keys one layer's walk of each pool read — a window layer's at most
    8 a lane and step — and both pools' blocks in use; the loop's records
    carry the same."""
    from mxnet_tpu.serving.obs import loop_records

    cfg, params, _eng, text, _want = served
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=3)
    reqs = [eng.submit([int(t) for t in text[a:b]], 30)
            for a, b in ((0, 5), (5, 45), (50, 120))]
    eng.step()
    mid = eng.stats()["hybrid"]
    assert mid["full_blocks_used"] > 0 and mid["window_blocks_used"] > 0
    _drain(eng)
    stats = eng.stats()
    moe_, hyb = stats["moe"], stats["hybrid"]
    assert moe_["routed_pairs"] == 2 * moe_["layer_tokens"]
    assert moe_["pairs"] == sum(map(sum, moe_["tokens_per_expert"]))
    assert 0 < moe_["pairs"] < moe_["routed_pairs"]
    steps = sum(len(r.generated) - 1 for r in reqs)
    assert hyb["lane_steps"] == steps
    # a step at context c (its own token included) reads c keys of the
    # full pool and min(c, 8) of the window pool
    ctxs = [n + j for r, n in zip(reqs, (5, 40, 70))
            for j in range(1, len(r.generated))]
    assert hyb["full_ctx_tokens"] == sum(ctxs)
    assert hyb["window_ctx_tokens"] == sum(min(c, WINDOW) for c in ctxs)
    assert hyb["full_blocks_used"] == hyb["window_blocks_used"] == 0
    recs = list(eng.obs._ring)
    assert sum(r.full_ctx_tokens for r in recs) == hyb["full_ctx_tokens"]
    assert sum(r.window_ctx_tokens for r in recs) == hyb["window_ctx_tokens"]
    assert loop_records  # the accessor the benchmark's readers window
    st = stats["state"]
    assert st["window_blocks_freed"] > 0 and st["full_pool_readers"] == 2


# ---------------------------------------------------------------- two pools
def test_two_pools_of_unequal_rows_are_booked_and_freed_together():
    """Admission books both pools or neither; a window block of 16 is freed
    every 16 tokens while the full pool's stay; a stream holds at most
    ``ceil((window + block + chunk - 1) / block)`` window blocks."""
    from mxnet_tpu.serving.kv_cache import KVCacheOOM

    scfg = C.serving_config(tiny())
    eng = ServingEngine(scfg, seed=2)
    assert eng.pool.spec.k_rows == eng.pool.spec.v_rows == (4, 128)
    assert eng.window_pool.spec.k_rows == (8, 128)
    assert eng.pool.k_pages.shape[2:] == (4, BS, 128)
    assert eng.window_pool.k_pages.shape[2:] == (8, BS, 128)
    assert eng.pool.spec.head_major and eng.window_pool.spec.head_major
    # a dry window pool refuses the admission and books nothing
    hog = eng.window_pool.alloc(eng.window_pool.available())
    req = eng.submit(list(range(1, 20)), 4)
    with pytest.raises(KVCacheOOM):
        eng.streams.admit(req, 19)
    assert req.wblocks in ([], None) or not any(req.wblocks)
    eng.window_pool.free(hog)
    freed = []
    while eng.has_work():
        eng.step()
        freed.append(eng.streams.blocks_freed)
    assert req.state == FINISHED
    long = eng.submit(list(range(1, 12)), 100)
    held = []
    while eng.has_work():
        eng.step()
        held.append((eng.window_pool.used(), eng.pool.used()))
    assert long.state == FINISHED and eng.streams.blocks_freed >= 100 // BS
    per_stream = -(-(WINDOW + BS + eng._chunk - 1) // BS)
    assert max(w for w, _f in held) <= per_stream
    assert max(f for _w, f in held) >= 111 // BS     # the full pool's stay
    assert eng.pool.used() == eng.window_pool.used() == 0


def test_a_dry_pool_preempts_the_youngest_and_replays_it():
    """Recompute preemption releases BOTH pools' blocks; the replayed
    stream's tokens are an unpressed engine's."""
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
    assert eng.pool.used() == len(hogged) and eng.window_pool.used() == 0


def test_concurrent_is_sequential():
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, VOCAB, k)) for k in (5, 17, 40, 28)]
    scfg = C.serving_config(tiny())
    together = ServingEngine(scfg, seed=3).generate(prompts, 30)
    alone = ServingEngine(scfg, seed=3)
    assert together == [alone.generate([p], 30)[0] for p in prompts]


# ------------------------------------------------------------- the kernels
@pytest.mark.parametrize("window,sink", [(None, False), (8, True),
                                         (24, True), (8, False)])
def test_the_paged_kernel_takes_narrow_values_a_sink_and_shared_lengths(
        window, sink):
    """Interpret mode: head-major pages, V rows narrower than K rows, four
    query lanes a K/V row under ONE context length a stream, the sink as
    the online softmax's start state — against the XLA lowering, whose
    sink is a column of the scores."""
    rng = np.random.RandomState(1)
    g, r, bs, wk, wv, nb, b = 2, 4, 8, 128, 128 // 1, 6, 5
    kp = jnp.asarray(rng.randn(2, 20, g, bs, wk), jnp.float32)
    vp = jnp.asarray(rng.randn(2, 20, g, bs, wv), jnp.float32)
    vp = vp[..., :wv]
    q = jnp.asarray(rng.randn(b, r, g, wk) * 0.3, jnp.float32)
    ctx = np.array([[1], [48], [8], [9], [33]], np.int32)
    tables = rng.randint(1, 20, (b, nb)).astype(np.int32)
    sk = jnp.asarray(rng.randn(r, g) + 1.0, jnp.float32) if sink else None
    kw = dict(sm_scale=0.2, layer=1, window=window, head_major=True,
              sink=sk)
    want = A.paged_attention_multi_reference(q, kp, vp, tables, ctx, **kw)
    got = A._paged_pallas_multi(q, kp, vp, tables, ctx, interpret=True,
                                name="paged_window_walk", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if sink:    # the sink takes mass: the result is not the plain one's
        plain = A.paged_attention_multi_reference(
            q, kp, vp, tables, ctx, **dict(kw, sink=None))
        assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("s,window", [(128, None), (256, 8), (384, 130)])
def test_the_flash_forward_names_the_kv_head_under_a_window(s, window):
    """Interpret mode: four query heads a K/V head found through the index
    map, the blocks behind a window's band skipped, the sink from the
    log-sum-exp — against dense attention with the sink as a column."""
    rng = np.random.RandomState(2)
    h, hk, d, dv = 8, 2, 48, 32
    q = jnp.asarray(rng.randn(1, h, s, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(1, hk, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(1, hk, s, dv), jnp.float32)
    sink = jnp.asarray(rng.randn(h) + 1.0, jnp.float32)
    out, lse = A._pallas_forward(q, k, v, True, 0.2, block_q=128,
                                 block_k=128, interpret=True, window=window,
                                 kv_group=h // hk, name="flash_gqa_fwd")
    got = out * jax.nn.sigmoid(lse - sink[None, :, None])[..., None]
    kr, vr = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, kr) * 0.2
    at = jnp.arange(s)
    seen = at[None, :] <= at[:, None]
    if window is not None:
        seen = seen & (at[:, None] - at[None, :] < window)
    sc = jnp.where(seen, sc, -jnp.inf)
    col = jnp.broadcast_to(sink[None, :, None, None], (1, h, s, 1))
    p = jax.nn.softmax(jnp.concatenate([sc, col], -1), -1)[..., :s]
    want = jnp.einsum("bhqk,bhkd->bhqd", p, vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and the public entry's CPU lowering is the same arithmetic
    np.testing.assert_allclose(
        np.asarray(A.flash_attention_gqa(q, k, v, 0.2, window, sink)),
        np.asarray(want), atol=2e-5)


# ----------------------------------------------------------- configuration
def test_serving_config_refuses_what_two_pools_cannot_do_yet():
    cfg = tiny()
    cfg["engine"]["prefix_cache"] = True
    with pytest.raises(ValueError, match="prefix_cache needs .*a shared "
                                         "prefix's window blocks are freed"):
        C.serving_config(cfg)
    cfg["engine"].update(prefix_cache=False, spec_k=2)
    with pytest.raises(ValueError, match="spec_k > 0 needs .*a verify pass "
                                         "against two pools"):
        C.serving_config(cfg)
    # a model of "full" layers alone passes a stateful model's refusals:
    # the step program's one query lane a stream refuses it all the same
    cfg["model"]["layer_kinds"] = ["full"] * 7
    with pytest.raises(ValueError, match="spec_k > 0 needs what plain "
                                         "grouped-query attention's step "
                                         "program .one query lane"):
        C.serving_config(cfg)
    cfg["model"]["layer_kinds"] = tiny()["model"]["layer_kinds"]
    cfg["engine"].update(prefix_cache=None, spec_k=0)
    scfg = C.serving_config(cfg)
    assert scfg.prefix_cache is False and scfg.gqa and scfg.hybrid
    assert scfg.stateful and not scfg.latent
    full, window = scfg.cache_specs()
    assert (full.k_rows, full.v_rows) == ((4, 128), (4, 128))
    assert (window.k_rows, window.v_rows) == ((8, 128), (8, 128))
    assert scfg.expert_layers == 6 and scfg.experts_here == (0, 4)
    model = {k: v for k, v in cfg["model"].items() if k != "vocab"}

    def bad(match, **changed):
        with pytest.raises(ValueError, match=match):
            ServingConfig(**dict(model, vocab_size=VOCAB, **changed))

    bad("takes 'swa', 'full' and 'kda' layers alone",
        layer_kinds=["full", "swa", "swa", "mamba", "swa", "swa", "full"])
    bad("do not share 3 K/V heads", swa_kv_heads=3)
    bad("an even rope_dim of at most head_dim", rope_dim=50)
    bad("pos 'rope' or 'none'", pos="learned")
    bad("attn_form must be", attn_form="mqa")
    bad("'swa' layers need window", window=0)
    with pytest.raises(ValueError, match="belong to a model with "
                                         "layer_kinds"):
        M.ModelConfig(attn_form="gqa")


def test_the_earlier_models_keys_are_what_they_were():
    """``attn_form`` and what goes with it stand behind the thirty-eight
    fields of a model with kinds, and only where the form is not "diff":
    Phi-4-mini-flash's and dots.vlm1's keys (and so their programs' cache
    keys) are the thirty-eight they were; a one-block model's the
    fourteen."""
    import json

    def key_of(name):
        cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                          name + ".json")))
        return ServingConfig.from_json(cfg).key()

    for name in ("phi4-mini-flash-bf16", "dots-vlm1-ep16-bf16"):
        key = key_of(name)
        assert len(key) == 38 and "diff" not in key and "gqa" not in key
    assert len(key_of("gpt2-medium-fp32")) == 14
    mine = key_of("mimo-v2.5-ep16-bf16")
    assert len(mine) == 43 and mine[38:] == ("gqa", 8, 1e4, True, 0.707)


def test_param_shapes_are_the_cuts():
    """The published widths, recounted from ``param_shapes``: the table of
    PERF.md section 4."""
    import json

    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "mimo-v2.5-ep16-bf16.json")))
    shapes = M.param_shapes(ServingConfig.from_json(cfg))

    def count(prefix):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if k.startswith(prefix))

    assert shapes["layer0_attn_in_weight"] == (64 * 192 + 4 * 320, 4096)
    assert shapes["layer1_attn_in_weight"] == (64 * 192 + 8 * 320, 4096)
    assert shapes["layer1_attn_sink"] == (64,)
    assert "layer0_attn_sink" not in shapes and "layer6_attn_sink" not in shapes
    assert count("layer0_attn") == 89128960
    assert count("layer1_attn") == 94371904
    assert count("layer0_") == 290463744                # the dense layer
    assert count("layer1_") == 498082112                # a window layer
    assert count("layer6_") == 492839168                # a full layer
    assert sum(int(np.prod(s)) for s in shapes.values()) == 3429955392


# ------------------------------------------------------- the wrong servers
def test_the_sound_server_passes_where_the_wrong_ones_fail(served):
    cfg, params, _eng, _text, _want = served
    sound = W.reading(cfg, C, params, "sound", 5)
    assert sound["worst"] < 1e-4 and sound["held"]["worst"] < 1e-4


@pytest.mark.parametrize("name", ["no_sink", "window_64", "bases_swapped",
                                  "rope_all_lanes", "freed_block_read",
                                  "window_kv_not_written"])
def test_a_wrong_server_is_far_from_the_reference(served, name):
    """``tools/wrong_servers.py``'s six faults of this model, each planted
    in an engine of its own: the probe reads them a hundred times further
    from the reference than the sound engine (the case above). ("window_64"
    plants a window of 64 in the programs: the tiny model's is 8, so it
    reads LONGER there.)"""
    cfg, params, _eng, _text, _want = served
    out = W.reading(cfg, C, params, name, 5)
    assert out["quartile"] > 1e-2 or out["decode_quartile"] > 1e-2, out
    assert M._sink.__module__ == M.__name__         # the patches are undone
    assert M.paged_attention_multi is A.paged_attention_multi


def test_the_harness_calls_a_wrong_server_not_correct():
    """``tools/wrong_servers.py --cell``: ``benchmark/run.py`` over the
    rehearsal cell with every decode step's window K/V thrown away. The
    comparison that refuses it is the driver's own, in the run's last line,
    by the decoded half alone."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "wrong_servers.py"),
         "--cell", "mimo-tiny", "--rehearsal", "--faults",
         "window_kv_not_written", "--seeds", "3300000041", "--seconds", "2"],
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
