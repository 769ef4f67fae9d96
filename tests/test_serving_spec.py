"""Speculative-decoding suite (docs/serving.md §speculative-decoding):
multi-query paged-attention numerics (reference vs per-lane single-query
vs the Pallas kernel in interpret mode), the greedy-acceptance
bit-identity contract against target-only decoding and the
contiguous-cache oracle, preemption invisibility with spec on, the
flat-compile-count gate, and acceptance accounting — capped by a slow
e2e driving 32 concurrent shared-prefix HTTP streams with speculative
decoding AND prefix sharing on.

Host-side only: part of tier-1 (tests/conftest.py pins jax to the CPU);
``ci/run_tests.sh serving`` runs the serving files alone, slow cases
included.
"""
import importlib
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import compileobs, telemetry  # noqa: E402
from mxnet_tpu.ops import attention as A  # noqa: E402
from mxnet_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from mxnet_tpu.serving import model as smodel  # noqa: E402

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
tlm = importlib.import_module("mxnet_tpu.models.transformer_lm")

CFG = dict(vocab_size=23, num_layers=2, model_dim=32, num_heads=2,
           ffn_dim=48, max_len=64)
SEED = 3


def _config(**over):
    kw = dict(CFG, block_size=8, num_blocks=64, max_batch=8,
              prefills_per_step=4)
    kw.update(over)
    return ServingConfig(**kw)


def _decode_executor(params):
    dec = tlm.get_decode_symbol(seq_len=CFG["max_len"], **CFG)
    ex = dec.simple_bind(ctx=mx.cpu(), grad_req="null", data=(1, 1))
    for n, a in ex.arg_dict.items():
        if n in params:
            a[:] = params[n]
    return ex


def _oracle_generate(ex, prompt, n_new, max_len=None):
    max_len = max_len or CFG["max_len"]
    for a in ex.aux_dict.values():
        a[:] = 0
    out, t, nxt = [], 0, None
    for tok in prompt:
        probs = tlm.decode_step(ex, [tok], t, max_len)
        t += 1
        nxt = int(np.argmax(probs[0]))
    for _ in range(n_new):
        out.append(nxt)
        probs = tlm.decode_step(ex, [nxt], t, max_len)
        t += 1
        nxt = int(np.argmax(probs[0]))
    return out


# ---------------------------------------------------------------------------
# multi-query paged attention numerics
# ---------------------------------------------------------------------------


def _multi_case(b=3, t=3, h=2, d=8, bs=4, nb_pool=16, nb_table=4, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, t, h, d).astype(np.float32)
    k_pages = rng.randn(nb_pool, bs, h, d).astype(np.float32)
    v_pages = rng.randn(nb_pool, bs, h, d).astype(np.float32)
    tables = rng.randint(1, nb_pool, size=(b, nb_table)).astype(np.int32)
    # per-lane context lengths including edge lanes: 0 (masked-out) and
    # the full window
    ctx = rng.randint(1, bs * nb_table + 1, size=(b, t)).astype(np.int32)
    ctx[0, 0] = 0
    ctx[-1, -1] = bs * nb_table
    return q, k_pages, v_pages, tables, ctx


def test_multi_reference_matches_per_lane_single_query():
    """Lane t of the multi-query pass must equal a single-query call with
    that lane's own context length — the verify pass is exactly k+1
    independent decode-step attentions sharing one dispatch."""
    q, kp, vp, tables, ctx = _multi_case()
    out = np.asarray(A.paged_attention_multi_reference(q, kp, vp, tables,
                                                       ctx))
    for t in range(q.shape[1]):
        ref = np.asarray(A.paged_attention_reference(
            q[:, t], kp, vp, tables, ctx[:, t]))
        np.testing.assert_allclose(out[:, t], ref, rtol=1e-5, atol=1e-5)


# (the multi-query Pallas kernel in interpret mode against the reference:
# tests/test_paged_pages.py::test_pallas_interpret_matches_reference, which
# took this file's case as its "plain-2x8-bs4" parameter)


def test_multi_zero_context_lane_is_zero_pinned():
    """A lane with context 0 (nothing valid to attend to) must output
    exactly zero from both implementations — not softmax garbage."""
    q, kp, vp, tables, ctx = _multi_case(seed=2)
    ctx[1, :] = 0           # a whole row of dead lanes
    ctx[2, 0] = 0           # dead lane in a live row (ctx_max > 0)
    ref = np.asarray(A.paged_attention_multi_reference(q, kp, vp, tables,
                                                       ctx))
    pal = np.asarray(A._paged_pallas_multi(q, kp, vp, tables, ctx,
                                           sm_scale=q.shape[-1] ** -0.5,
                                           interpret=True))
    assert np.all(ref[1] == 0.0) and np.all(pal[1] == 0.0)
    assert np.all(ref[2, 0] == 0.0) and np.all(pal[2, 0] == 0.0)
    np.testing.assert_allclose(pal, ref, rtol=1e-5, atol=1e-5)


def test_multi_t1_equals_single_query_path():
    q, kp, vp, tables, ctx = _multi_case(t=1, seed=3)
    multi = np.asarray(A.paged_attention_multi_reference(q, kp, vp, tables,
                                                         ctx))
    single = np.asarray(A.paged_attention_reference(q[:, 0], kp, vp,
                                                    tables, ctx[:, 0]))
    np.testing.assert_allclose(multi[:, 0], single, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the verify step function
# ---------------------------------------------------------------------------


def test_extend_matches_sequential_decode_steps():
    """extend() over a T-token window == T sequential decode() calls:
    same tokens at the same positions produce the same greedy argmax and
    the same K/V writes (the window K/V is scattered before attention)."""
    cfg = _config()
    params = smodel.as_device_params(smodel.random_params(cfg, seed=SEED),
                                     cfg)
    import jax.numpy as jnp

    shape = (cfg.num_layers, cfg.num_blocks, cfg.block_size, cfg.num_heads,
             cfg.model_dim // cfg.num_heads)
    rng = np.random.RandomState(5)
    prompt = [int(x) for x in rng.randint(0, cfg.vocab_size, 10)]
    nb = cfg.max_len // cfg.block_size
    table = np.zeros((1, nb), np.int32)
    table[0, :3] = [1, 2, 3]
    toks = np.zeros((1, cfg.max_len), np.int32)
    toks[0, :len(prompt)] = prompt
    window = [int(x) for x in rng.randint(0, cfg.vocab_size, 3)]

    def prefilled_pages():
        kp = jnp.zeros(shape, cfg.kv_dtype)
        vp = jnp.zeros(shape, cfg.kv_dtype)
        _t, _l, kp, vp = smodel.prefill(params, toks,
                                        np.int32(len(prompt)), table[0],
                                        kp, vp, cfg)
        return kp, vp

    # path A: T sequential single-token decode steps
    kp, vp = prefilled_pages()
    seq_toks = []
    for j, w in enumerate(window):
        pos = np.array([len(prompt) + j], np.int32)
        ctx = pos + 1
        nxt, _l, kp, vp = smodel.decode(params, np.array([w], np.int32),
                                        pos, table, ctx, kp, vp, cfg)
        seq_toks.append(int(np.asarray(nxt)[0]))
    k_seq, v_seq = np.asarray(kp), np.asarray(vp)

    # path B: ONE extend() pass over the same window
    kp, vp = prefilled_pages()
    T = len(window)
    toks2 = np.array([window], np.int32)
    poss2 = np.array([[len(prompt) + j for j in range(T)]], np.int32)
    ctx2 = poss2 + 1
    nxt2, _l, kp, vp = smodel.extend(params, toks2, poss2, table, ctx2,
                                     kp, vp, cfg)
    ext_toks = [int(x) for x in np.asarray(nxt2)[0]]
    np.testing.assert_allclose(np.asarray(kp), k_seq, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vp), v_seq, rtol=1e-5, atol=1e-6)
    assert ext_toks == seq_toks


def test_extend_overflow_lane_poisoned():
    """Window lanes at/past max_len must emit token -1 (the engine stops
    the stream's acceptance walk there) and drop their cache writes."""
    cfg = _config()
    params = smodel.as_device_params(smodel.random_params(cfg, seed=SEED),
                                     cfg)
    import jax.numpy as jnp

    shape = (cfg.num_layers, cfg.num_blocks, cfg.block_size, cfg.num_heads,
             cfg.model_dim // cfg.num_heads)
    kp = jnp.zeros(shape, cfg.kv_dtype)
    vp = jnp.zeros(shape, cfg.kv_dtype)
    nb = cfg.max_len // cfg.block_size
    table = np.ones((1, nb), np.int32)
    poss = np.array([[cfg.max_len - 1, cfg.max_len]], np.int32)
    toks = np.array([[1, 2]], np.int32)
    ctx = poss + 1
    nxt, _l, kp, vp = smodel.extend(params, toks, poss, table, ctx, kp, vp,
                                    cfg)
    nxt = np.asarray(nxt)
    assert nxt[0, 0] >= 0, "in-range lane must decode normally"
    assert nxt[0, 1] == -1, "overflow lane must be poisoned"


# ---------------------------------------------------------------------------
# engine: bit-identity, acceptance, compiles
# ---------------------------------------------------------------------------


def _workload(rng, n, vocab, pmax=20):
    return [[int(x) for x in rng.randint(0, vocab, rng.randint(1, pmax))]
            for _ in range(n)]


@pytest.mark.parametrize("k", [1, 2, 3])
def test_spec_decode_bit_identical_self_draft(k):
    """Self-drafting (draft == target): every emitted stream equals
    target-only decoding token for token, and acceptance is high (the
    draft IS the target; only window-edge truncation loses proposals)."""
    rng = np.random.RandomState(17 + k)
    prompts = _workload(rng, 6, CFG["vocab_size"])
    prompts.append([1] * 8)     # block-boundary prompt
    n_new = [int(x) for x in rng.randint(1, 14, len(prompts))]
    base = ServingEngine(_config(spec_k=0), seed=SEED)
    want = base.generate(prompts, n_new)
    eng = ServingEngine(_config(spec_k=k, draft="self"), seed=SEED)
    got = eng.generate(prompts, n_new)
    assert got == want
    spec = eng.stats()["spec"]
    assert spec["enabled"] and spec["k"] == k
    assert 0 < spec["accepted_tokens"] <= spec["proposed_tokens"]


@pytest.mark.parametrize("pps", [None, 4, 1])
def test_spec_decode_same_tokens_whatever_a_step_admits(pps):
    """The speculative path admits through the same pass: with no
    ``prefills_per_step`` seven prompts and eight lanes are ONE group (the
    draft's prefills dispatched beside the target's), with 4 two groups,
    with 1 seven, and every stream is target-only decoding's to the bit."""
    rng = np.random.RandomState(23)
    prompts = _workload(rng, 6, CFG["vocab_size"])
    prompts.append([1] * 8)     # block-boundary prompt
    n_new = [int(x) for x in rng.randint(2, 14, len(prompts))]
    want = ServingEngine(_config(spec_k=0), seed=SEED).generate(
        prompts, n_new)
    eng = ServingEngine(_config(spec_k=2, prefills_per_step=pps), seed=SEED)
    assert eng.generate(prompts, n_new) == want
    stats = eng.stats()
    assert stats["spec"]["accepted_tokens"] > 0
    assert stats["prefill"]["groups"] == {None: 1, 4: 2, 1: 7}[pps]
    assert stats["prefill"]["stopped_by"]["cap"] == {None: 0, 4: 1, 1: 6}[pps]
    assert eng.pool.used() == 0


def test_spec_decode_bit_identical_tiny_draft():
    """A WRONG draft (tiny random preset, disjoint weights) must not
    change a single emitted token — greedy acceptance emits only the
    target's argmax at every reached lane."""
    rng = np.random.RandomState(29)
    prompts = _workload(rng, 6, CFG["vocab_size"])
    n_new = [int(x) for x in rng.randint(1, 14, len(prompts))]
    base = ServingEngine(_config(spec_k=0), seed=SEED)
    want = base.generate(prompts, n_new)
    eng = ServingEngine(_config(spec_k=2, draft="tiny"), seed=SEED)
    assert eng.draft_config.num_layers == 1   # the zoo preset
    got = eng.generate(prompts, n_new)
    assert got == want
    spec = eng.stats()["spec"]
    assert spec["proposed_tokens"] > 0
    assert 0.0 <= spec["acceptance_rate"] <= 1.0


def test_spec_decode_matches_contiguous_oracle():
    cfg = _config(spec_k=2)
    eng = ServingEngine(cfg, seed=SEED)
    ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
    rng = np.random.RandomState(31)
    prompts = _workload(rng, 4, cfg.vocab_size)
    got = eng.generate(prompts, 12)
    for p, g in zip(prompts, got):
        assert g == _oracle_generate(ex, p, 12)


def test_spec_preemption_invisible():
    """Recompute preemption under speculative decoding: evicted streams
    replay and still emit exactly the oracle's tokens."""
    cfg = _config(spec_k=2, num_blocks=13, max_batch=4)
    eng = ServingEngine(cfg, seed=SEED)
    ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
    rng = np.random.RandomState(13)
    prompts = [[int(x) for x in rng.randint(0, cfg.vocab_size, 8)]
               for _ in range(4)]
    pre0 = telemetry.counter("serving.preemptions").value
    got = eng.generate(prompts, [18] * 4)
    assert telemetry.counter("serving.preemptions").value > pre0, \
        "workload sized to force eviction saw none"
    for p, g in zip(prompts, got):
        assert g == _oracle_generate(ex, p, 18)
    assert eng.pool.used() == 0


def test_spec_with_prefix_sharing_bit_identical():
    """Both tentpole features on at once: shared-prefix concurrent
    streams, speculative decoding, outputs equal the oracle."""
    cfg = _config(spec_k=2, prefix_cache=True, prefills_per_step=1)
    eng = ServingEngine(cfg, seed=SEED)
    prefix = list(range(1, 17))
    prompts = [prefix + t for t in ([], [17], [18, 19])]
    reqs = [eng.submit(p, 10) for p in prompts]
    while any(not r.finished() for r in reqs):
        eng.step()
    assert eng.pool.prefix_stats()["hits"] >= 2
    ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
    for p, r in zip(prompts, reqs):
        assert list(r.generated) == _oracle_generate(ex, p, 10)


def test_spec_compile_count_flat_after_warmup():
    """Fixed k per engine: after warmup() no spec traffic may compile —
    no per-k, per-step, or per-acceptance recompiles (nonce-free keys;
    serving.draft + serving.verify ride the same bucket discipline)."""
    cfg = _config(spec_k=2)
    eng = ServingEngine(cfg, seed=SEED)
    eng.warmup()

    def counts():
        return {p["program"]: p["compile_count"]
                for p in compileobs.program_table()
                if p["program"].startswith("serving.")}

    warm = counts()
    assert warm.get("serving.draft", 0) >= 1
    assert warm.get("serving.verify", 0) >= 1
    rng = np.random.RandomState(41)
    prompts = _workload(rng, 6, cfg.vocab_size)
    eng.generate(prompts, [10] * len(prompts))
    assert counts() == warm, "steady-state spec traffic recompiled"


def test_spec_k_zero_engine_has_no_draft_programs():
    cfg = _config(spec_k=0)
    eng = ServingEngine(cfg, seed=SEED)
    assert not eng._spec
    assert eng._draft_params is None and eng._draft_kp is None


def test_spec_negative_k_rejected():
    with pytest.raises(ValueError, match="spec_k"):
        _config(spec_k=-1)


def test_unknown_draft_preset_rejected():
    with pytest.raises(ValueError, match="draft"):
        ServingEngine(_config(spec_k=1, draft="nope"), seed=SEED)


# ---------------------------------------------------------------------------
# slow e2e: 32 concurrent shared-prefix HTTP streams, spec + sharing on
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_e2e_32_shared_prefix_http_streams_spec_and_sharing(tmp_path):
    """Acceptance: 32 concurrent shared-prefix requests through
    tools/serve.py with MXNET_SERVING_SPEC_K=2 and the prefix cache on
    are bit-identical to sequential single-stream decoding, with a flat
    compile count after warmup and prefix hits on /stats."""
    port = 18317
    n_req = 32
    cfg = _config(num_blocks=257, max_batch=32)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_SERVING_SPEC_K="2", MXNET_SERVING_DRAFT="self",
               MXNET_SERVING_PREFIX_CACHE="1")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tools", "serve.py"),
         "--port", str(port), "--vocab", str(cfg.vocab_size),
         "--num-layers", str(cfg.num_layers),
         "--model-dim", str(cfg.model_dim),
         "--num-heads", str(cfg.num_heads),
         "--ffn-dim", str(cfg.ffn_dim), "--max-len", str(cfg.max_len),
         "--block-size", str(cfg.block_size),
         "--num-blocks", str(cfg.num_blocks),
         "--max-batch", str(cfg.max_batch), "--seed", str(SEED),
         "--warmup"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    base = "http://127.0.0.1:%d" % port

    def get(path, timeout=5):
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return json.loads(r.read())

    try:
        deadline = time.time() + 180
        while True:
            try:
                assert get("/healthz")["ok"]
                break
            except (OSError, AssertionError):
                if time.time() > deadline:
                    raise RuntimeError("server never came up")
                time.sleep(0.5)

        rng = np.random.RandomState(23)
        shared = [int(x) for x in rng.randint(0, cfg.vocab_size, 16)]
        prompts = [shared + [int(x) for x in
                             rng.randint(0, cfg.vocab_size,
                                         rng.randint(1, 9))]
                   for _ in range(n_req)]
        n_new = [int(x) for x in rng.randint(1, 16, n_req)]
        results = [None] * n_req
        errors = []

        def fire(i):
            body = json.dumps({"tokens": prompts[i],
                               "max_new_tokens": n_new[i]}).encode()
            req = urllib.request.Request(base + "/generate", data=body)
            try:
                with urllib.request.urlopen(req, timeout=600) as r:
                    results[i] = json.loads(r.read())
            except Exception as e:  # surfaced below with the index
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        assert not errors, errors
        assert all(r is not None for r in results)

        stats = get("/stats")
        compiles_after_load = {n: c["count"]
                               for n, c in stats["compiles"].items()}
        assert "serving.draft" in compiles_after_load
        assert "serving.verify" in compiles_after_load
        assert stats["completed"] >= n_req
        assert stats["prefix"]["hits"] >= 1, \
            "32 shared-prefix admissions produced zero index hits"
        assert stats["spec"]["accepted_tokens"] > 0

        # sequential single-stream oracle, same seeded weights
        ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
        for i in range(n_req):
            want = _oracle_generate(ex, prompts[i], n_new[i])
            assert results[i]["tokens"] == want, \
                "request %d: %s != %s" % (i, results[i]["tokens"], want)

        # flat compile count: re-fire a subset over the same buckets
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert {n: c["count"]
                for n, c in get("/stats")["compiles"].items()} \
            == compiles_after_load, "steady-state spec traffic recompiled"
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
