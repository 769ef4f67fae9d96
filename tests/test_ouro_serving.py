"""A stack that every token goes through several times (Ouro's: sandwich
norms, RoPE, a SiLU-gated FFN, ``loop_steps`` passes with the same weights
and a K/V cache a pass) through ``ServingEngine``, against the plain fp32
reference of ``benchmark/configs/ouro-2.6b-bf16.py`` — logits, not tokens —
at a small size on the CPU: 3 layers, 64 wide, 4 heads of 16, FFN 160,
vocab 256; 1, 2 and 4 passes; float32 and bfloat16 pages.

* prefill; prefill then single decode steps; a batch of mixed lengths; a
  preempted and replayed stream; a prefix-cache hit against a cold prompt;
  the verify pass against decode steps; ``decode_chunk`` against single
  steps of the same executable; a speculative engine against a plain one;
* a server whose passes all read and write pass 0's cache FAILS;
* the pool's layers, ``nbytes()`` and ``cow`` cover every pass; the
  counters and the walks' booking; a program holds ONE pass's layer bodies;
* the configurations there were keep the keys they had.

Weights are drawn at unit gain with gammas off one, so that every norm and
every pass moves the logits at 64 wide.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving import engine as E
from mxnet_tpu.serving import model as M
from mxnet_tpu.serving.obs import loop_records

from chunk_cases import chunk_equals_single_steps, lane, tables_for
from test_olmoe_serving import Capture, one_step, serve  # noqa: F401
from tools import wrong_servers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config_module():
    path = os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b-bf16.py")
    spec = importlib.util.spec_from_file_location("ouro_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


C = _config_module()
VOCAB, LAYERS = 256, 3
LOOPS = [1, 2, 4]
DTYPES = ["float32", "bfloat16"]
#: bfloat16 against the fp32 reference at this size: the worst row of the
#: sound cases below reads 2.3% of its sequence's largest logit and the
#: probe's worst row 3.6% of its own; the decoded rows of a server whose
#: passes share pass 0's cache read 100%
BF16_TOL = 6e-2


def tiny(dtype="float32", loops=3, **engine):
    """A configuration file's worth of a tiny looped stack."""
    eng = dict(block_size=8, num_blocks=33, max_batch=4, spec_k=0,
               kv_dtype=dtype, prefix_cache=True)
    eng.update(engine)
    return {
        "name": "ouro-test",
        "model": dict(vocab=VOCAB, num_layers=LAYERS, model_dim=64,
                      num_heads=4, head_dim=16, ffn_dim=160, ffn_gated=True,
                      max_len=128, norm="rms", norm_eps=1e-6, pos="rope",
                      rope_theta=1e6, bias=False, loop_steps=loops,
                      post_norm=True),
        "engine": eng, "weights_dtype": dtype,
        "reference": {"seq_pad": 128, "gen_max": 48, "probe_len": 96,
                      "probe_rows": 12, "probe_prefix": [8, 64],
                      "probe_decode": [4, 24]}}


def weights(cfg, seed=1):
    scfg = C.serving_config(cfg)
    rng = np.random.RandomState(seed)
    dtype = jnp.dtype(cfg["weights_dtype"])
    out = {}
    for name, shape in sorted(M.param_shapes(scfg).items()):
        if name.endswith("_gamma"):
            w = rng.uniform(0.5, 1.5, shape)
        elif name == "embed_weight" or name.endswith("_bias"):
            w = rng.randn(*shape)
        else:
            w = rng.randn(*shape) / np.sqrt(shape[-1])
        out[name] = jnp.asarray(w, jnp.float32).astype(dtype)
    return out


def engine(cfg, params=None, **kw):
    return ServingEngine(C.serving_config(cfg),
                         arg_params=params or weights(cfg), **kw)


def prompts_of(lengths, seed=1):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, VOCAB, n)] for n in lengths]


def worst_logit_error(cfg, eng, cap, reqs):
    """Largest |served - reference| logit over the captured rows, in units
    of the reference's largest |logit| of that sequence."""
    ref = C.reference_logits(cfg)
    worst = 0.0
    for req in reqs:
        seq = list(req.prompt) + list(req.generated)
        want = ref(eng.params, seq[:-1])
        assert len(cap.rows[req.rid]) >= len(req.generated)
        for n_ctx, got in cap.rows[req.rid]:
            worst = max(worst, np.abs(got - want[n_ctx - 1]).max()
                        / np.abs(want).max())
    return worst


def tol(dtype):
    return 1e-4 if dtype == "float32" else BF16_TOL


SHAPES = {
    "prefill_then_steps": dict(lengths=[21], n_new=[14]),
    "mixed_batch": dict(lengths=[3, 17, 30, 9], n_new=[9, 5, 12, 7]),
    # 7 usable blocks of 8 for three streams that want 4-5 each
    "preempted_and_resumed": dict(lengths=[9, 12, 10], n_new=[24, 24, 24],
                                  engine=dict(num_blocks=9)),
}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("loops", LOOPS, ids="loops{}".format)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_prefill_and_paged_decode_match_the_reference(shape, loops, dtype,
                                                      one_step):
    spec = SHAPES[shape]
    cfg = tiny(dtype, loops, **spec.get("engine", {}))
    eng = engine(cfg)
    cap = Capture(eng)
    reqs = serve(eng, prompts_of(spec["lengths"]), spec["n_new"])
    if shape == "preempted_and_resumed":
        assert eng.scheduler.preempt_count > 0
        assert any(r.preemptions for r in reqs)
    assert worst_logit_error(cfg, eng, cap, reqs) < tol(dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("loops", LOOPS, ids="loops{}".format)
def test_prefill_alone_matches_the_reference_at_every_prefix(loops, dtype):
    """``prefill_logits`` (the benchmark's probe) over a one-block prompt
    (the slice-update path) and longer ones, with and without forced decode
    steps behind it; and the exit distribution is one."""
    cfg = tiny(dtype, loops)
    eng = engine(cfg)
    text = prompts_of([50], seed=3)[0]
    want, p = C.reference_logits(cfg)(eng.params, text, exits=True)
    scale = np.abs(want).max()
    for n, start in ((5, None), (8, None), (23, None), (50, None),
                     (30, 8), (50, 37)):
        got = eng.prefill_logits(text[:n], decode_from=start)
        assert np.abs(got - want[n - 1]).max() / scale < tol(dtype), (n, start)
    assert p.shape == (50, loops)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    assert (p >= 0).all() and (loops == 1 or p[:, 0].std() > 0)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("loops", LOOPS, ids="loops{}".format)
def test_prefix_cache_hit_matches_a_cold_prompt(loops, dtype, one_step):
    """A block's rows in every pass's cache are a function of the prefix
    alone: a prompt that maps three shared blocks is served the logits of
    the same prompt on a cold engine, and the reference's."""
    cfg = tiny(dtype, loops)
    eng = engine(cfg)
    cap = Capture(eng)
    shared = prompts_of([24], seed=7)[0]            # three full blocks
    first = eng.submit(shared + [1, 2, 3], 12)
    eng.step()                  # its blocks are indexed while it runs
    second = eng.submit(shared + [9, 8], 6)
    while not (first.finished() and second.finished()):
        eng.step()
    assert eng.pool.prefix_stats()["hit_blocks"] >= 3
    assert worst_logit_error(cfg, eng, cap, [first, second]) < tol(dtype)
    cold = engine(cfg)
    assert cold.generate([shared + [9, 8]], [6])[0] == list(second.generated)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("loops", LOOPS, ids="loops{}".format)
def test_the_verify_pass_is_four_decode_steps(loops, dtype):
    """``extend`` over T = 4 lanes against four one-token steps from the
    same pages: the same logits, the same K/V in every pass's part."""
    cfg = tiny(dtype, loops)
    scfg = C.serving_config(cfg)
    params = weights(cfg)
    shape, _ = scfg.cache_specs().full.shape(9, 8)
    assert shape == (LAYERS, loops * 9, 8, 4, 16)
    pool = jnp.zeros(shape, jnp.dtype(dtype))
    prompt = prompts_of([11])[0]
    toks = np.zeros((1, 16), np.int32)
    toks[0, :11] = prompt
    table = np.array([3, 5], np.int32)
    _t, _l, kp, vp = M.prefill(params, toks, np.int32(11), table, pool, pool,
                               scfg)
    window = np.array([[7, 100, 33, 5], [2, 2, 150, 9]], np.int32)
    tables = np.array([[3, 5, 6, 0], [3, 5, 7, 0]], np.int32)
    pos = 11 + np.arange(4, dtype=np.int32)[None].repeat(2, 0)
    nxt, logits, ekp, evp = M.extend(params, window, pos, tables, pos + 1,
                                     kp, vp, scfg)
    dkp, dvp = kp, vp
    for t in range(4):
        n1, l1, dkp, dvp = M.decode(params, window[:, t], pos[:, t], tables,
                                    pos[:, t] + 1, dkp, dvp, scfg)
        scale = float(jnp.abs(l1).max())
        assert float(jnp.abs(l1 - logits[:, t]).max()) < (
            1e-5 if dtype == "float32" else BF16_TOL) * scale
        if dtype == "float32":
            np.testing.assert_array_equal(n1, nxt[:, t])
    live = [p * 9 + b for p in range(loops) for b in (3, 5, 6, 7)]
    for a, b in ((ekp, dkp), (evp, dvp)):
        np.testing.assert_allclose(
            np.asarray(a[:, live], np.float32),
            np.asarray(b[:, live], np.float32),
            atol=1e-5 if dtype == "float32" else 0.1)
        # every pass wrote its own part: none of the live blocks is empty
        assert all(float(jnp.abs(a[:, p * 9 + blk]).max()) > 0
                   for p in range(loops) for blk in (3, 5))


@pytest.mark.parametrize("loops", [2, 4], ids="loops{}".format)
def test_chunk_program_equals_single_steps(chunk, loops):
    """The decode chunk (the steps' loop OUTSIDE, the passes' loop inside)
    == single steps of the same executable: tokens, logits and every
    pass's pages, with lanes that end inside the chunk."""
    cfg = tiny("float32", loops)
    scfg = C.serving_config(cfg)
    params = weights(cfg)
    nb = scfg.max_len // scfg.block_size
    lanes = [lane(5, 6, 9), lane(7, 21, 2), lane(9, 40, 9),
             lane(2, scfg.max_len - 2, 9), lane(0, 0, 0)]
    tables = tables_for(lanes, nb, scfg.block_size)
    rng = np.random.RandomState(5)
    shape, _ = scfg.cache_specs().full.shape(65, 8)
    assert shape == (LAYERS, loops * 65, 8, 4, 16)
    caches = {k: jnp.asarray(rng.randn(*shape), jnp.float32) for k in "kv"}
    step = jax.jit(lambda *a: M.decode_chunk(params, *a, scfg, chunk))

    def program(tok, pos, ctx, left, eos, n, c):
        rows, logits, kp, vp = step(tok, pos, tables, ctx, left, eos,
                                    np.int32(n), c["k"], c["v"])
        # a part's block 0 is that pass's trash: keep it out of the
        # comparison as chunk_cases keeps block 0
        trash = jnp.arange(loops) * 65
        return rows, logits, {"k": kp.at[:, trash].set(0),
                              "v": vp.at[:, trash].set(0)}, None

    rows, _ = chunk_equals_single_steps(program, scfg.max_len, lanes, caches,
                                        chunk)
    lanes[2] = lane(9, 40, 9, eos=int(rows[min(1, chunk - 1), 2]))
    rows, _ = chunk_equals_single_steps(program, scfg.max_len, lanes, caches,
                                        chunk)
    live = (rows >= 0).sum(axis=1)
    assert live[0] == 4 and live[-1] == (1 if chunk == 4 else 4)


@pytest.mark.parametrize("loops", [2, 4], ids="loops{}".format)
def test_chunked_and_speculative_engines_emit_the_single_step_stream(
        loops, chunk, monkeypatch):
    prompts, n_new = prompts_of([9, 12, 10, 5]), [24, 17, 22, 7]
    cfg = tiny("float32", loops, num_blocks=9)      # preempts and replays
    monkeypatch.setattr(E, "DECODE_CHUNK", 1)
    want = engine(cfg).generate(prompts, n_new)
    monkeypatch.setattr(E, "DECODE_CHUNK", chunk)
    eng = engine(cfg)
    assert eng.generate(prompts, n_new) == want
    assert eng.scheduler.preempt_count > 0
    if chunk == 1:      # the self-draft proposes the target's own tokens
        spec = engine(tiny("float32", loops, spec_k=3))
        assert spec.generate(prompts, n_new) == want
        st = spec.stats()
        assert st["spec"]["acceptance_rate"] > 0.8
        assert st["looped"]["passes_per_step"] == loops


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_server_whose_passes_share_one_cache_fails(dtype):
    """``tools/wrong_servers.py``'s ``shared_cache`` (every pass reads and
    writes pass 0's cache layers: the paper's quarter-size cache, which is
    a different result). Prefill alone cannot tell (a pass's keys are then its own either
    way: the last pass's overwrite the others' in the pool, but attention
    in prefill reads the projections); a decode step reads the LAST pass's
    K/V in every pass, and the decoded rows are far off."""
    cfg = tiny(dtype, 4)
    params = weights(cfg)
    sound = C.make_probe(cfg)(params, engine(cfg, params).prefill_logits, 7)
    assert sound["quartile"] < tol(dtype)
    with wrong_servers.planted("shared_cache", cfg["model"]):
        seen = C.make_probe(cfg)(params, engine(cfg, params).prefill_logits,
                                 7)
    assert seen["prefill_quartile"] < tol(dtype)
    assert seen["decode_quartile"] > 4 * BF16_TOL
    assert seen["quartile"] == seen["decode_quartile"]


def test_the_pool_holds_every_pass_and_cow_copies_every_part():
    cfg = tiny("bfloat16", 4)
    eng = engine(cfg)
    pool = eng.pool
    assert (pool.spec.layers, pool.spec.parts, pool.spec.cache_layers) == (
        LAYERS, 4, 12)
    assert pool.k_pages.shape == (LAYERS, 4 * 33, 8, 4, 16)
    assert pool.spec.shape(33, 8) == (pool.k_pages.shape, pool.v_pages.shape)
    per_token = 12 * 2 * 4 * 16 * 2             # cache layers x K, V x H hd
    assert pool.block_nbytes() == 8 * per_token
    assert pool.nbytes() == 33 * 8 * per_token \
        == pool.k_pages.nbytes + pool.v_pages.nbytes
    assert eng.stats()["kv_pool_bytes"] == pool.nbytes()
    assert eng.stats()["looped"]["cache_layers"] == 12
    # a block is ONE allocation unit: 32 usable, whatever the passes
    assert pool.num_usable == 32 == eng.stats()["kv_blocks_total"]
    # cow: every part of the shared block, bit for bit, and nothing else
    rng = np.random.RandomState(0)
    pool.k_pages, pool.v_pages = (
        jnp.asarray(rng.randn(*pool.k_pages.shape), jnp.bfloat16)
        for _ in range(2))
    before = np.asarray(pool.k_pages, np.float32)
    (b,) = pool.alloc(1)
    pool.incref([b])
    nb = pool.cow(b)
    assert nb != b and pool.refcount(b) == pool.refcount(nb) == 1
    after = np.asarray(pool.k_pages, np.float32)
    for part in range(4):
        np.testing.assert_array_equal(after[:, part * 33 + nb],
                                      before[:, part * 33 + b])
    untouched = [i for i in range(4 * 33) if i % 33 != nb]
    np.testing.assert_array_equal(after[:, untouched], before[:, untouched])


def test_a_shared_block_written_through_cow_keeps_both_streams_right(
        monkeypatch):
    """The engine's own cow guard over a looped pool and a looped draft's
    pages: a block shared by force is copied in every part before the
    write, and both streams go on as if never shared."""
    monkeypatch.setattr(E, "DECODE_CHUNK", 1)
    cfg = tiny("float32", 2, spec_k=2)
    prompt = prompts_of([12])[0]
    want = engine(cfg).generate([prompt], [10])[0]
    eng = engine(cfg)
    req = eng.submit(prompt, 10)
    eng.step()                                  # prefilled: 12 tokens
    tail = req.blocks[1]                        # holds positions 8..15
    eng.pool.incref([tail])                     # someone else maps it
    while not req.finished():
        eng.step()
    assert list(req.generated) == want
    assert eng.pool.cow_copies == 1 and tail not in req.blocks
    assert eng.pool.refcount(tail) == 1
    eng.pool.free([tail])


def test_the_counters_and_the_walks_are_booked_a_pass(monkeypatch):
    monkeypatch.setattr(E, "DECODE_CHUNK", 4)
    cfg = tiny("float32", 4)
    eng = engine(cfg)
    t0 = __import__("time").time()
    c0 = telemetry.counter("serving.looped.passes").value
    live0 = telemetry.counter("serving.paged.live_blocks").value
    reqs = serve(eng, prompts_of([5, 19]), [9, 6])
    st = eng.stats()
    steps = st["decode"]["inner_steps"] + st["prefill"]["prompts"]
    assert st["looped"] == {"passes": 4 * steps, "steps": steps,
                            "passes_per_step": 4.0, "cache_layers": 12}
    assert telemetry.counter("serving.looped.passes").value - c0 == 4 * steps
    # a walk a live lane, step AND pass: contexts 6..13 and 20..24 in
    # blocks of 8, four times
    walks = sum(-(-c // 8) for c in range(6, 14)) \
        + sum(-(-c // 8) for c in range(20, 25))
    assert st["paged"]["live_blocks"] == 4 * walks
    assert telemetry.counter("serving.paged.live_blocks").value - live0 \
        == 4 * walks
    assert st["paged"]["table_slots"] == 4 * (8 + 5) * 16
    recs = [r for r in loop_records(t0) if r in eng.obs._ring]
    assert sum(r.passes for r in recs) == 4 * steps
    assert sum(r.live_blocks for r in recs) == 4 * walks
    assert sum(r.chunk_steps + r.prefills for r in recs) == steps
    assert [len(r.generated) for r in reqs] == [9, 6]
    # a stack that runs once books none of it
    plain = engine(tiny("float32", 1))
    serve(plain, prompts_of([5]), [4])
    assert "looped" not in plain.stats()
    assert all(r.passes == 0 for r in plain.obs._ring)


def _paged_calls(jaxpr):
    """Calls of the paged attention in a jaxpr, sub-jaxprs included: on
    the CPU each is one ``platform_index`` switch."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "platform_index"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _paged_calls(sub)
    return n


@pytest.mark.parametrize("loops", LOOPS, ids="loops{}".format)
def test_a_program_holds_one_pass_of_layer_bodies(loops):
    """The decode program's text holds L paged calls and L layers' worth of
    matmuls whatever the passes: they are a loop inside the program."""
    cfg = tiny("float32", loops)
    scfg = C.serving_config(cfg)
    params = weights(cfg)
    pool = jnp.zeros((LAYERS, loops * 9, 8, 4, 16), jnp.float32)
    ints = np.zeros(2, np.int32)
    args = (params, ints, ints, np.zeros((2, 16), np.int32), ints + 1, ints,
            ints - 1, np.int32(1), pool, pool)

    def fn(*a):
        return M.decode_chunk(*a, scfg, 4)

    jaxpr = jax.make_jaxpr(fn)(*args)
    assert _paged_calls(jaxpr.jaxpr) == LAYERS
    text = jax.jit(fn).lower(*args).as_text()
    # q/k/v, o, gate/up and down a layer, and the head: not a pass times that
    assert text.count("stablehlo.dot_general") < 2 * (4 * LAYERS + 1) + 8 * LAYERS
    assert text.count("stablehlo.while") == (2 if loops > 1 else 1)


# --------------------------------------------------------- ModelConfig --
def test_the_configurations_there_were_keep_their_keys():
    """``key()`` feeds every graph and compile cache key: GPT-2's and
    OLMoE's tiny configurations give the parent's fourteen-field tuples, to
    the value, and a model with kinds its thirty-eight."""
    gpt2 = M.ModelConfig(50, 2, 32, 4, 64, 64)
    assert gpt2.key() == (50, 2, 32, 4, 64, 64, "layer", "learned", 10000.0,
                          False, 8, 0, 0, True)
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                           "olmoe-tiny.json")) as f:
        olmoe = ServingConfig.from_json(json.load(f))
    assert olmoe.key() == (256, 2, 64, 4, 32, 128, "rms", "rope", 10000.0,
                           True, 16, 8, 2, False)
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                           "phi4flash-tiny.json")) as f:
        phi4 = ServingConfig.from_json(json.load(f))
    assert len(phi4.key()) == 38 and phi4.key()[-1] is None \
        and "loop_steps" not in M.ModelConfig.__slots__[:38]
    # a one-block model that sets any of the four has all four in its key
    looped = C.serving_config(tiny(loops=4))
    assert looped.key()[14:] == (True, 1e-6, 4, True)
    assert M.ModelConfig(50, 2, 32, 4, 64, 64, norm_eps=1e-6).key()[14:] \
        == (False, 1e-6, 1, False)


def test_what_a_looped_stack_refuses():
    base = dict(vocab_size=50, num_layers=2, model_dim=32, num_heads=4,
                ffn_dim=64, max_len=64)
    with pytest.raises(ValueError, match="before its last pass"):
        M.ModelConfig(loop_steps=4, early_exit_threshold=0.9, **base)
    with pytest.raises(ValueError, match="loop_steps must be >= 1"):
        M.ModelConfig(loop_steps=0, **base)
    with pytest.raises(ValueError, match="one-block model"):
        M.ModelConfig(loop_steps=2, layer_kinds=["mamba", "mamba"],
                      pos="none", **base)
    with pytest.raises(ValueError, match="routed experts"):
        M.ModelConfig(loop_steps=2, num_experts=4, experts_per_tok=2, **base)
    ok = M.ModelConfig(loop_steps=4, post_norm=True, ffn_gated=True,
                       norm="rms", pos="rope", bias=False, **base)
    assert ok.cache_specs().full.cache_layers == 8
    names = set(M.param_shapes(ok))
    assert {"early_exit_gate_weight", "early_exit_gate_bias",
            "layer0_ln1_post_gamma", "layer1_ln2_post_gamma"} <= names
    assert M.param_shapes(ok)["layer0_ffn1_weight"] == (128, 32)
    plain = set(M.param_shapes(M.ModelConfig(**base)))
    assert not [n for n in plain if "post" in n or "exit" in n]


def test_tools_serve_builds_the_engine_from_the_configuration_file():
    """``tools/serve.py --model-config`` takes the configuration file's
    ``model`` and ``engine`` objects as it does every other block's."""
    import argparse

    from tools import serve

    eng = serve.build_engine(argparse.Namespace(
        model_config=os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                                  "ouro-tiny.json"),
        checkpoint=None, seed=3, max_queue=None, default_timeout_ms=None))
    assert (eng.config.loop_steps, eng.config.post_norm,
            eng.pool.spec.cache_layers) == (3, True, 9)
    assert eng.params["layer0_ln1_post_gamma"].dtype == jnp.bfloat16
    assert "early_exit_gate_weight" in eng.params
    (out,) = eng.generate([[1, 2, 3, 4, 5]], [6])
    assert len(out) == 6 and eng.stats()["looped"]["passes_per_step"] == 3.0


# ------------------------------------------------------- the benchmark --
def _rehearse(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal",
         "--workload", "ouro-tiny", "--seed", "5", "--seconds", "2",
         "--trace", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    return [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]


def test_the_rehearsal_cell_runs_and_reads_its_passes(tmp_path):
    lines = _rehearse(tmp_path)
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["observed"]["looped_passes_per_step"]["value"] == 3.0
    engine_line = next(l for l in lines if l.get("bench") == "engine")
    # 65 blocks of 16 tokens x 9 cache layers x K, V x 64 lanes in bfloat16
    assert engine_line["pool_bytes"] == 65 * 16 * 9 * 2 * 64 * 2
    seen = next(l for l in lines if l.get("bench") == "reference")["logits"]
    assert seen["quartile"] <= seen["band"]


def test_the_harness_calls_a_shared_cache_not_correct(tmp_path):
    """``tools/wrong_servers.py --cell``: the rehearsal cell through
    ``benchmark/run.py`` over a server whose passes all use pass 0's cache.
    The probe's decoded half is far off, and the run is ``correct`` false
    by the probe's limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "wrong_servers.py"),
         "--cell", "ouro-tiny", "--rehearsal", "--faults", "shared_cache",
         "--seeds", "5", "--seconds", "2"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    seen = next(l for l in lines if l.get("bench") == "reference")["logits"]
    assert seen["prefill_quartile"] < seen["band"] < seen["decode_quartile"]
    assert lines[-1] == {"fault": "shared_cache", "seed": 5,
                         "cell": "ouro-tiny", "through": "benchmark/run.py",
                         "correct": False}
    assert any("first quartile" in l.get("problem", "") for l in lines)
