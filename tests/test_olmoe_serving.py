"""OLMoE's block (RMSNorm, QK-norm, RoPE, 64-of-8 routed SiLU experts with
nothing dropped) through ``ServingEngine``, against the plain fp32 reference
of ``benchmark/configs/olmoe-1b-7b-bf16.py`` — logits, not tokens — at a
small size on the CPU:

* prefill then paged decode against the reference's full forward, fp32 at
  1e-4 and bf16 at the configuration's band, over a prompt shorter than a
  block, one that crosses blocks, a batch of mixed lengths, a preempted and
  replayed request and a prefix-cache hit; ``extend`` against ``decode``;
* five deliberately wrong servers each FAIL the cell's checks;
* ``ops/moe.py`` against a loop over experts; RoPE against a closed form;
* GPT-2's ``ModelConfig`` defaults give the shapes and the bits they did.

The arithmetic tests draw every matrix at unit gain (the experts louder,
gammas off one), so that each mechanism moves the logits at 64 wide. In
bfloat16 a rounding can flip a near-tied expert choice and move a row's
logits by more than any useful band, on the CPU as on the chip: the seeds
below are fixed, the CPU is deterministic, and the bf16 cases say that THIS
seeded traffic is served inside the band. The tests of what the cell's
``correct`` can see draw their weights as the cell does (``cell_like``).
"""
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import moe
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving import engine as E
from mxnet_tpu.serving import model as M

from chunk_cases import chunk_equals_single_steps, lane, tables_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config_module():
    path = os.path.join(ROOT, "benchmark", "configs", "olmoe-1b-7b-bf16.py")
    spec = importlib.util.spec_from_file_location("olmoe_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


C = _config_module()
VOCAB = 211


def tiny(dtype="float32", **engine):
    """A configuration file's worth of a tiny OLMoE: 2 layers, 64 wide,
    4 heads of 16, 8 experts of 32, 2 a token."""
    eng = dict(block_size=8, num_blocks=65, max_batch=4, spec_k=0,
               kv_dtype=dtype, prefix_cache=True)
    eng.update(engine)
    return {
        "model": dict(vocab=VOCAB, num_layers=2, model_dim=64, num_heads=4,
                      head_dim=16, ffn_dim=32, max_len=128, norm="rms",
                      pos="rope", rope_theta=10000.0, qk_norm=True,
                      num_experts=8, experts_per_tok=2, bias=False),
        "engine": eng, "weights_dtype": dtype,
        "reference": {"seq_pad": 128, "gen_max": 48}}


def weights(cfg, seed=1):
    """Seeded weights in the configuration's type: unit gain, the experts
    louder (3) and attention and the router quieter (0.5), gammas off
    one."""
    scfg = ServingConfig.from_json(cfg)
    rng = np.random.RandomState(seed)
    dtype = jnp.dtype(cfg["weights_dtype"])
    out = {}
    for name, shape in sorted(M.param_shapes(scfg).items()):
        if name.endswith("_gamma"):
            w = rng.uniform(0.5, 1.5, shape)
        elif name == "embed_weight":
            w = rng.randn(*shape)
        else:
            gain = 3.0 if "experts" in name else 0.5
            w = rng.randn(*shape) * gain / np.sqrt(shape[-1])
        out[name] = jnp.asarray(w, jnp.float32).astype(dtype)
    return out


def engine(cfg, params=None, **kw):
    return ServingEngine(ServingConfig.from_json(cfg),
                         arg_params=params or weights(cfg), **kw)


@pytest.fixture
def one_step(monkeypatch):
    """Engines built in the test decode one step a dispatch, so that
    :class:`Capture` sees every step's logits (a chunk hands back its
    lanes' last). The executable is the chunk's own, run with n = 1."""
    monkeypatch.setattr(E, "DECODE_CHUNK", 1)


class Capture:
    """Record the logits of every prefill and decode an engine runs:
    ``rows[rid] = [(tokens in context, logits (V,)), ...]``."""

    def __init__(self, eng):
        self.rows = {}
        now = {}
        prefill_fn, decode_fn = eng._prefill_fn, eng._decode_fn
        start_prefill, run_decode = eng._start_prefill, eng._run_decode

        def _prefill_fn(params, toks, length, *rest):
            out = prefill_fn(params, toks, length, *rest)
            logits = np.asarray(out[1], np.float32)
            # a row of logits a prompt of the pack, in the pack's order
            for i, (req, replay) in enumerate(now["pack"]):
                self.rows.setdefault(req.rid, []).append(
                    (len(replay), logits[i]))
            return out

        def _decode_fn(params, toks, poss, tables, ctx, *rest, **chunk):
            out = decode_fn(params, toks, poss, tables, ctx, *rest, **chunk)
            logits = np.asarray(out[1], np.float32)
            for i, req in enumerate(now["reqs"]):
                self.rows[req.rid].append((int(ctx[i]), logits[i]))
            return out

        def _start_prefill(pack, rung, grouped):
            now["pack"] = pack
            return start_prefill(pack, rung, grouped)

        def _run_decode(reqs):
            now["reqs"] = list(reqs)
            return run_decode(reqs)

        eng._prefill_fn, eng._decode_fn = _prefill_fn, _decode_fn
        eng._start_prefill, eng._run_decode = _start_prefill, _run_decode


def serve(eng, prompts, n_new):
    """Submit, drive to the end, return the requests."""
    reqs = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
    while any(not r.finished() for r in reqs):
        eng.step()
    assert all(r.state == "finished" for r in reqs), \
        [(r.state, r.error) for r in reqs]
    return reqs


def worst_logit_error(cfg, eng, cap, reqs, over=max):
    """Largest |served - reference| logit of each captured row, in units of
    the reference's largest |logit| of that sequence; the worst row's (or
    ``over`` the rows)."""
    ref = C.reference_logits(cfg)
    errors = []
    for req in reqs:
        seq = list(req.prompt) + list(req.generated)
        want = ref(eng.params, seq[:-1])
        scale = np.abs(want).max()
        assert len(cap.rows[req.rid]) >= len(req.generated)
        for n_ctx, got in cap.rows[req.rid]:
            errors.append(np.abs(got - want[n_ctx - 1]).max() / scale)
    return over(errors)


def prompts_of(lengths, seed=1):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, VOCAB, n)] for n in lengths]


def check(cfg, eng, cap, reqs, dtype):
    """float32: every served logit within 1e-4 of the reference's (in units
    of the sequence's largest). bfloat16: the configuration's band, which
    is a band on the SERVED TOKEN (its reference logit against the
    position's largest) — rounding can flip a near-tied expert choice, and
    the logits of such a row move by more than any band that still tells
    the wrong servers below apart; the typical row stays inside it."""
    if dtype == "float32":
        assert worst_logit_error(cfg, eng, cap, reqs) < 1e-4
        return
    score = C.make_reference(cfg)
    for req in reqs:
        off, _matches = score(eng.params, list(req.prompt),
                              list(req.generated))
        assert off == []
    assert worst_logit_error(cfg, eng, cap, reqs, np.median) < C.LOGIT_RTOL


SHAPES = {
    "shorter_than_a_block": dict(lengths=[5], n_new=[6]),
    "crosses_blocks": dict(lengths=[21], n_new=[14]),
    "mixed_batch": dict(lengths=[3, 17, 30, 9], n_new=[9, 5, 12, 7]),
    # 7 usable blocks of 8 for three streams that want 4-5 each
    "preempted_and_replayed": dict(lengths=[9, 12, 10], n_new=[24, 24, 24],
                                   engine=dict(num_blocks=9)),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_prefill_and_paged_decode_match_the_reference(shape, dtype,
                                                      one_step):
    spec = SHAPES[shape]
    cfg = tiny(dtype, **spec.get("engine", {}))
    eng = engine(cfg)
    cap = Capture(eng)
    pre0 = telemetry.counter("serving.preemptions").value
    reqs = serve(eng, prompts_of(spec["lengths"]), spec["n_new"])
    if shape == "preempted_and_replayed":
        assert telemetry.counter("serving.preemptions").value > pre0
        assert any(r.preemptions for r in reqs)
    check(cfg, eng, cap, reqs, dtype)
    # nothing dropped: every live token's k experts, in every layer
    m = cfg["model"]
    st = eng.stats()["moe"]
    tokens = sum(n for rows in cap.rows.values() for n, _l in rows[:1]) \
        + sum(len(rows) - 1 for rows in cap.rows.values())
    if shape != "preempted_and_replayed":     # a replay prefills again
        assert st["pairs"] == tokens * m["experts_per_tok"] * m["num_layers"]
    # ... and exactly k times what the engine itself counted on the host,
    # replays included (what the benchmark's driver holds a window to)
    assert st["pairs"] == m["experts_per_tok"] * st["layer_tokens"] > 0
    # the same tokens x k in every layer
    assert all(sum(row) * m["num_layers"] == st["pairs"]
               for row in st["tokens_per_expert"])
    assert st["layer_steps"] % m["num_layers"] == 0
    assert 0 < st["experts_touched"] <= st["layer_steps"] * m["num_experts"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefix_cache_hit_matches_the_reference(dtype, one_step):
    cfg = tiny(dtype)
    eng = engine(cfg)
    cap = Capture(eng)
    shared = prompts_of([24], seed=7)[0]            # three full blocks
    first = eng.submit(shared + [1, 2, 3], 12)
    eng.step()                  # its blocks are indexed while it runs
    second = eng.submit(shared + [9, 8], 6)
    while not (first.finished() and second.finished()):
        eng.step()
    assert eng.pool.prefix_stats()["hit_blocks"] >= 3
    check(cfg, eng, cap, [first, second], dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_extend_is_four_decode_steps(dtype):
    """The verify pass over T = 4 lanes against four one-token steps from
    the same pages: the same logits, the same K/V written."""
    cfg = tiny(dtype)
    scfg = ServingConfig.from_json(cfg)
    params = weights(cfg)
    shape, _ = scfg.cache_specs().full.shape(9, 8)
    assert shape == (2, 9, 8, 4, 16)
    pool = jnp.zeros(shape, jnp.dtype(dtype))
    prompt = prompts_of([11])[0]
    toks = np.zeros((1, 16), np.int32)
    toks[0, :11] = prompt
    table = np.array([3, 5], np.int32)
    _t, _l, kp, vp, _load = M.prefill(params, toks, np.int32(11), table,
                                      pool, pool, scfg)
    window = np.array([[7, 100, 33, 5], [2, 2, 150, 9]], np.int32)
    tables = np.array([[3, 5, 6, 0], [3, 5, 7, 0]], np.int32)
    pos = 11 + np.arange(4, dtype=np.int32)[None].repeat(2, 0)
    nxt, logits, ekp, evp, load = M.extend(params, window, pos, tables,
                                           pos + 1, kp, vp, scfg)
    assert int(load.sum()) == 2 * 4 * 2 * 2       # B x T x k x layers
    dkp, dvp = kp, vp
    for t in range(4):
        n1, l1, dkp, dvp, _ = M.decode(params, window[:, t], pos[:, t],
                                       tables, pos[:, t] + 1, dkp, dvp, scfg)
        tol = 1e-5 if dtype == "float32" else C.LOGIT_RTOL
        scale = float(jnp.abs(l1).max())
        assert float(jnp.abs(l1 - logits[:, t]).max()) < tol * scale
        if dtype == "float32":
            np.testing.assert_array_equal(n1, nxt[:, t])
    for a, b in ((ekp, dkp), (evp, dvp)):
        np.testing.assert_allclose(
            np.asarray(a[:, 3:], np.float32), np.asarray(b[:, 3:],
                                                         np.float32),
            atol=1e-5 if dtype == "float32" else 0.1)


def test_chunk_program_equals_single_steps(chunk):
    """The decode chunk over routed experts == single steps of the same
    executable: tokens, logits, pages, and each step's load — which counts
    that step's LIVE lanes only (a lane past its length cap or its EOS,
    and the padded row, are not in it)."""
    cfg = tiny()
    scfg = ServingConfig.from_json(cfg)
    params = weights(cfg)
    nb = scfg.max_len // scfg.block_size
    lanes = [lane(5, 6, 9), lane(7, 21, 2), lane(9, 40, 9),
             lane(2, scfg.max_len - 2, 9), lane(0, 0, 0)]
    tables = tables_for(lanes, nb, scfg.block_size)
    rng = np.random.RandomState(5)
    shape, _ = scfg.cache_specs().full.shape(65, 8)
    assert shape == (2, 65, 8, 4, 16)
    caches = {k: jnp.asarray(rng.randn(*shape), jnp.float32) for k in "kv"}
    step = jax.jit(lambda *a: M.decode_chunk(params, *a, scfg, chunk))

    def program(tok, pos, ctx, left, eos, n, c):
        rows, logits, kp, vp, loads = step(tok, pos, tables, ctx, left, eos,
                                           np.int32(n), c["k"], c["v"])
        return rows, logits, {"k": kp, "v": vp}, loads

    rows, _ = chunk_equals_single_steps(program, scfg.max_len, lanes, caches,
                                        chunk)
    lanes[2] = lane(9, 40, 9, eos=int(rows[min(1, chunk - 1), 2]))
    rows, loads = chunk_equals_single_steps(program, scfg.max_len, lanes,
                                            caches, chunk)
    live = (rows >= 0).sum(axis=1)           # lanes alive at each step
    assert live[0] == 4 and live[-1] == (1 if chunk == 4 else 4)
    # k experts a live lane and layer, a step: nothing more, nothing less
    np.testing.assert_array_equal(loads.sum(axis=2), 2 * live[:, None]
                                  * np.ones((1, 2), np.int64))


def test_chunked_serving_drops_nothing_and_counts_live_lanes(
        chunk, monkeypatch):
    """The engine over chunks of 1, 2 and 4 serves the tokens of one step
    a dispatch, and after EVERY step the experts' pairs are exactly k
    times the tokens the host booked (the cell's `correct` holds a window
    to it, to the unit) and `serving.decode_batch` has seen the live
    lane-steps — with streams that end inside a chunk, one by its EOS."""
    cfg = tiny(num_blocks=9)           # 8 blocks of 8: preempts and replays
    prompts, n_new = prompts_of([9, 12, 10, 5]), [24, 17, 22, 7]
    monkeypatch.setattr(E, "DECODE_CHUNK", 1)
    want = engine(cfg).generate(prompts, n_new)
    eos = want[1][9]
    want[1] = want[1][:want[1].index(eos) + 1]
    monkeypatch.setattr(E, "DECODE_CHUNK", chunk)
    eng = engine(cfg)
    batch0 = telemetry.totals("serving.decode_batch")
    tok0 = telemetry.counter("serving.generated_tokens").value
    reqs = [eng.submit(p, n, eos_id=eos if i == 1 else None)
            for i, (p, n) in enumerate(zip(prompts, n_new))]
    while any(not r.finished() for r in reqs):
        eng.step()
        st = eng.stats()
        assert st["moe"]["pairs"] == 2 * st["moe"]["layer_tokens"] > 0
        count, total = telemetry.totals("serving.decode_batch")
        assert count - batch0[0] == st["decode"]["inner_steps"]
        # a fresh prompt's first token is its prefill's; every other token
        # (a replay's prefill emits none) came from a live decode lane
        made = telemetry.counter("serving.generated_tokens").value - tok0
        assert total - batch0[1] == made - sum(
            r.first_token_t is not None for r in reqs)
    assert [list(r.generated) for r in reqs] == want
    assert eng.scheduler.preempt_count > 0
    assert st["decode"]["steps_per_dispatch"] > (1 if chunk > 1 else 0)


def test_speculative_engine_emits_the_target_stream():
    cfg = tiny()
    plain = engine(cfg).generate(prompts_of([6, 19]), [10, 8])
    eng = engine(tiny(spec_k=3))
    assert eng.generate(prompts_of([6, 19]), [10, 8]) == plain
    # the verify pass's lanes are counted like decode's tokens
    st = eng.stats()["moe"]
    assert st["pairs"] == 2 * st["layer_tokens"] > 0
    # and its blocks, to the window's last lane (at most 19 + 10 + 3
    # tokens: five blocks of 8), a stream and pass, over tables of 16
    paged = eng.stats()["paged"]
    lanes, rest = divmod(paged["table_slots"], 16)
    assert rest == 0 and 2 < lanes < 2 * 10
    assert lanes < paged["live_blocks"] <= 5 * lanes


def test_an_engine_without_experts_reports_no_moe_block():
    import warnings

    from mxnet_tpu.serving import model as lm

    scfg = ServingConfig(vocab_size=50, num_layers=1, model_dim=16,
                         num_heads=2, ffn_dim=32, max_len=32, block_size=8,
                         num_blocks=9, max_batch=2)
    eng = ServingEngine(scfg, arg_params=lm.random_params(scfg, seed=0))
    eng.generate([[1, 2, 3]], [4])
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert "moe" not in eng.stats()


# ------------------------------------- what the cell's `correct` can see
# The five wrong servers of ISSUE 26 against the configuration's own checks,
# on weights DRAWN AS THE CELL'S ARE: ``init_params`` of the configuration
# module with the cell's ``init`` (its ``expert_gain``), the standard
# deviation scaled so that a matmul has the gain it has at 2048 wide. Eight
# experts, four a token: "seven of eight" is three of four here, the
# smallest chosen weight dropped, as there.
CELL = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                   "olmoe-1b-7b-bf16.json")))


def cell_like(**model):
    cfg = tiny("bfloat16")
    cfg["model"].update(num_experts=8, experts_per_tok=4, vocab=512)
    cfg["model"].update(model)
    cfg["init"] = dict(CELL["init"], std=CELL["init"]["std"]
                       * (CELL["model"]["model_dim"] / 64) ** 0.5)
    cfg["reference"].update(probe_len=96, probe_rows=24)
    return cfg


def _float8_experts(params):
    out = dict(params)
    for name, w in params.items():
        if "_experts_" in name:
            out[name] = w.astype(jnp.float8_e4m3fn).astype(w.dtype)
    return out


def _renormalised(route):
    def wrong(x, router, k):
        w, e = route(x, router, k)
        return w / jnp.sum(w, axis=-1, keepdims=True), e
    return wrong


def _capacity_capped(route, capacity=3):
    """Pairs past an expert's first ``capacity`` (in token order) dropped:
    what ``parallel/moe.py`` does to an overfull expert in training."""
    def wrong(x, router, k):
        w, e = route(x, router, k)
        flat = e.reshape(-1)
        same = flat[:, None] == flat[None, :]
        rank = jnp.sum(jnp.tril(same, -1), axis=1)    # earlier pairs, same e
        return jnp.where(rank.reshape(e.shape) < capacity, w, 0.0), e
    return wrong


def _rope_off_k(position):
    def wrong(q, k, positions, cfg):
        return position(q, k, positions, cfg)[0], k
    return wrong


WRONG = {
    "experts_in_float8": dict(params=_float8_experts),
    "seven_of_eight_experts": dict(model=dict(experts_per_tok=3)),
    "renormalised_topk": dict(patch=(moe, "route", _renormalised)),
    "capacity_cap_drops_pairs": dict(patch=(moe, "route", _capacity_capped)),
    "rope_left_off_k": dict(patch=(M, "_position", _rope_off_k)),
}


def _verdict(cfg, params, served_cfg, served_params):
    """What the benchmark's driver reads of a server: the dense probe's
    first quartile, the re-scored positions outside the token band, and the
    "nothing dropped" count — references over the RIGHT weights."""
    from benchmark.drivers import serve_arch

    eng = engine(served_cfg, served_params)
    probe = C.make_probe(cfg)(params, eng.prefill_logits, 7)
    before = eng.stats()["moe"]
    prompts = prompts_of([13, 40, 25, 6])
    outs = eng.generate(prompts, [32, 32, 32, 32])
    score = C.make_reference(cfg)
    off = sum(len(score(params, p, g)[0]) for p, g in zip(prompts, outs))
    dropped = serve_arch.nothing_dropped(
        cfg, {"before": before, "after": eng.stats()["moe"]})
    return probe["quartile"], off, dropped


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bf16_serving_passes_the_cells_checks(seed):
    cfg = cell_like()
    params = C.init_params(cfg, seed)
    quartile, off, dropped = _verdict(cfg, params, cfg, params)
    assert quartile < C.PROBE_RTOL and off == 0 and dropped is None


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_server_fails_the_cells_checks(what, monkeypatch):
    """Each fails the dense probe's band; one expert fewer also fails the
    exact count. (The token band alone lets the quieter ones through at
    the published widths: PERF.md section 6, PR 26.)"""
    wrong = WRONG[what]
    cfg = cell_like()
    params = C.init_params(cfg, 3)
    served_cfg = cell_like(**wrong.get("model", {}))
    served = wrong.get("params", lambda p: p)(params)
    if "patch" in wrong:
        mod, name, make = wrong["patch"]
        monkeypatch.setattr(mod, name, make(getattr(mod, name)))
    quartile, _off, dropped = _verdict(cfg, params, served_cfg, served)
    assert quartile > C.PROBE_RTOL
    assert (dropped is not None) == (what == "seven_of_eight_experts")


def test_prefill_logits_are_the_prefills_and_book_nothing():
    cfg = tiny()
    eng = engine(cfg)
    prompt = prompts_of([21])[0]
    logits = eng.prefill_logits(prompt)
    assert logits.shape == (VOCAB,) and logits.dtype == np.float32
    assert eng.stats()["moe"]["pairs"] == 0 and eng.pool.used() == 0
    want = C.reference_logits(cfg)(eng.params, prompt)[-1]
    assert np.abs(logits - want).max() < 1e-4 * np.abs(want).max()
    assert eng.generate([prompt], [1])[0][0] == int(logits.argmax())
    with pytest.raises(ValueError):
        eng.prefill_logits([])


# ------------------------------------------------------------ ops/moe.py
def _loop_over_experts(x, router, gate, up, down, k):
    x = np.asarray(x, np.float64)
    logits = x @ np.asarray(router, np.float64).T
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    counts = np.zeros(router.shape[0], np.int64)
    for t in range(x.shape[0]):
        for e in np.argsort(-p[t], kind="stable")[:k]:
            g = np.asarray(gate[e], np.float64) @ x[t]
            u = np.asarray(up[e], np.float64) @ x[t]
            h = g / (1.0 + np.exp(-g)) * u
            out[t] += p[t, e] * (np.asarray(down[e], np.float64) @ h)
            counts[e] += 1
    return out, counts


def _moe_inputs(t, e=8, m=32, f=16, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(t, m).astype(np.float32),
            rng.randn(e, m).astype(np.float32),
            rng.randn(e, f, m).astype(np.float32) / np.sqrt(m),
            rng.randn(e, f, m).astype(np.float32) / np.sqrt(m),
            rng.randn(e, m, f).astype(np.float32) / np.sqrt(f))


@pytest.mark.parametrize("routing", ["random", "all_on_one_expert",
                                     "an_expert_with_none"])
def test_moe_ffn_is_the_loop_over_experts(routing):
    x, router, gate, up, down = _moe_inputs(24)
    if routing != "random":
        x = np.abs(x)                           # every router row's sign
    if routing == "all_on_one_expert":          # decides for every token
        router[5] = 40.0
    elif routing == "an_expert_with_none":
        router[2] = -40.0
    k = 3
    got, counts = jax.jit(functools.partial(moe.moe_ffn, k=k))(
        x, router, gate, up, down)
    want, want_counts = _loop_over_experts(x, router, gate, up, down, k)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == x.shape[0] * k
    if routing == "all_on_one_expert":
        assert int(counts[5]) == x.shape[0]
    if routing == "an_expert_with_none":
        assert int(counts[2]) == 0


def test_moe_ffn_counts_only_valid_tokens():
    x, router, gate, up, down = _moe_inputs(10)
    valid = np.arange(10) < 6
    out, counts = moe.moe_ffn(x, router, gate, up, down, 2, valid=valid)
    full, _ = moe.moe_ffn(x, router, gate, up, down, 2)
    assert int(counts.sum()) == 6 * 2
    np.testing.assert_array_equal(out, full)    # padded lanes still compute


def test_grouped_matmul_kernel_is_the_xla_grouped_matmul():
    """The TPU branch (jax's Pallas ``gmm``, here in interpret mode) and
    the branch every other platform takes give the same rows."""
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(40, 128), jnp.float32)
    w = jnp.asarray(rng.randn(4, 256, 128), jnp.float32) / 11.0
    sizes = jnp.asarray([17, 0, 20, 3], jnp.int32)
    want = moe._grouped_xla(rows, w, sizes)
    got = moe._grouped_pallas(rows, w, sizes, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ RoPE
@pytest.mark.parametrize("position", [0, 1, 4095])
def test_rope_against_the_closed_form(position):
    cfg = M.ModelConfig(10, 1, 32, 2, 8, 4096, pos="rope", head_dim=16)
    rng = np.random.RandomState(position)
    x = rng.randn(1, 1, 32).astype(np.float32)
    got = np.asarray(M._rope(jnp.asarray(x),
                             jnp.full((1, 1), position, jnp.int32), cfg))
    want = np.zeros((2, 16))
    heads = x.reshape(2, 16).astype(np.float64)
    for i in range(8):                  # lane i is paired with lane i + 8
        a = position * 10000.0 ** (-2.0 * i / 16)
        want[:, i] = heads[:, i] * np.cos(a) - heads[:, i + 8] * np.sin(a)
        want[:, i + 8] = heads[:, i + 8] * np.cos(a) + heads[:, i] * np.sin(a)
    np.testing.assert_allclose(got.reshape(2, 16), want, atol=2e-3
                               if position == 4095 else 1e-6)
    if position == 0:
        np.testing.assert_array_equal(got, x)


# ------------------------------------------------- GPT-2 stays what it was
def _gpt2_decode_as_it_was(params, tokens, positions, tables, ctx, kp, vp,
                           cfg):
    """``serving/model.py``'s decode before the shared layer body (PR 25),
    kept here as the yardstick of "unchanged op for op"."""
    from mxnet_tpu.ops.attention import paged_attention
    from mxnet_tpu.ops.registry import fp32_precision

    def ln(x, g, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-5) * g + b

    B = tokens.shape[0]
    m, hh = cfg.model_dim, cfg.num_heads
    bs, rows, lanes = kp.shape[2:]
    prec = fp32_precision(kp.dtype)
    page_ids = jnp.take_along_axis(tables, (positions // bs)[:, None],
                                   axis=1)[:, 0]
    slots = positions % bs
    pos_tab = params["pos_embed_weight"].reshape(cfg.max_len, m)
    x = (jnp.take(params["embed_weight"], tokens, axis=0)
         + jnp.take(pos_tab, positions, axis=0))[:, None, :]
    for i in range(cfg.num_layers):
        p = "layer%d" % i
        h = ln(x, params[p + "_ln1_gamma"], params[p + "_ln1_beta"])
        qkv = jnp.einsum("bsm,nm->bsn", h, params[p + "_attn_in_weight"],
                         precision=prec)
        q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
        kp = kp.at[i, page_ids, slots].set(k_new.reshape(B, rows, lanes))
        vp = vp.at[i, page_ids, slots].set(v_new.reshape(B, rows, lanes))
        attn = paged_attention(q.reshape(B, hh, m // hh), kp, vp, tables,
                               ctx, layer=i).reshape(B, 1, m)
        x = x + jnp.einsum("bsm,nm->bsn", attn,
                           params[p + "_attn_out_weight"], precision=prec)
        h = ln(x, params[p + "_ln2_gamma"], params[p + "_ln2_beta"])
        f = jnp.dot(h.reshape(B, m), params[p + "_ffn1_weight"].T,
                    precision=prec)
        f = jnp.maximum(f + params[p + "_ffn1_bias"], 0)
        f = jnp.dot(f, params[p + "_ffn2_weight"].T, precision=prec)
        x = x + (f + params[p + "_ffn2_bias"]).reshape(B, 1, m)
    x = ln(x, params["final_ln_gamma"], params["final_ln_beta"])
    return (jnp.dot(x.reshape(B, m), params["lm_head_weight"].T,
                    precision=prec) + params["lm_head_bias"]), kp, vp


def test_gpt2_defaults_give_the_shapes_they_did():
    cfg = M.ModelConfig(50, 2, 16, 2, 32, 24)
    assert cfg.key()[6:] == ("layer", "learned", 10000.0, False, 8, 0, 0,
                             True)
    shapes = M.param_shapes(cfg)
    per_layer = {"_ln1_gamma": (1, 1, 16), "_ln1_beta": (1, 1, 16),
                 "_ln2_gamma": (1, 1, 16), "_ln2_beta": (1, 1, 16),
                 "_attn_in_weight": (48, 16), "_attn_out_weight": (16, 16),
                 "_ffn1_weight": (32, 16), "_ffn1_bias": (32,),
                 "_ffn2_weight": (16, 32), "_ffn2_bias": (16,)}
    want = {"embed_weight": (50, 16), "pos_embed_weight": (1, 24, 16),
            "final_ln_gamma": (1, 1, 16), "final_ln_beta": (1, 1, 16),
            "lm_head_weight": (50, 16), "lm_head_bias": (50,)}
    for i in range(2):
        want.update({"layer%d%s" % (i, k): v for k, v in per_layer.items()})
    assert shapes == want
    assert ServingConfig(50, 2, 16, 2, 32, 24, block_size=8).key() == cfg.key()


def test_gpt2_decode_is_bit_identical_to_the_unshared_body():
    cfg = M.ModelConfig(97, 2, 64, 4, 128, 64)
    params = {k: jnp.asarray(v) for k, v in M.random_params(cfg, 3).items()}
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(2, 9, 16, 4, 16), jnp.float32)
    toks = np.array([5, 6, 9], np.int32)
    pos = np.array([20, 3, 17], np.int32)
    tables = np.array([[3, 4, 0, 0], [1, 0, 0, 0], [2, 5, 0, 0]], np.int32)
    want, wkp, wvp = jax.jit(functools.partial(
        _gpt2_decode_as_it_was, cfg=cfg))(params, toks, pos, tables,
                                          pos + 1, pool, pool)
    nxt, got, kp, vp = jax.jit(functools.partial(M.decode, cfg=cfg))(
        params, toks, pos, tables, pos + 1, pool, pool)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(kp, wkp)
    np.testing.assert_array_equal(vp, wvp)
    np.testing.assert_array_equal(nxt, np.argmax(want, -1))


def test_config_from_json_and_subset_warmup():
    cfg = tiny()
    scfg = ServingConfig.from_json(cfg)
    assert (scfg.vocab_size, scfg.head_dim, scfg.num_experts, scfg.pos) == (
        VOCAB, 16, 8, "rope")
    assert scfg.block_size == 8 and scfg.kv_dtype == np.float32
    assert "pos_embed_weight" not in M.param_shapes(scfg)
    assert M.param_shapes(scfg)["layer1_experts_down_weight"] == (8, 64, 32)
    eng = engine(cfg)

    def compiles():     # the program's record, shared by its buckets
        return (eng._prefill_jits[8].compile_totals()[0],
                eng._decode_jits[1].compile_totals()[0])

    p0, d0 = compiles()
    eng.warmup(prefill_buckets=[8])
    p1, d1 = compiles()
    eng.warmup(prefill_buckets=[16, 64])
    p2, d2 = compiles()
    eng.warmup(prefill_buckets=[8, 16, 64])
    assert p2 - p1 == 2 * (p1 - p0) > 0      # one compile a NEW bucket
    assert d1 - d0 > 0 and d2 == d1          # every decode bucket, once
    assert compiles() == (p2, d2)
    with pytest.raises(ValueError):
        eng.warmup(prefill_buckets=[12])
    # a step program hands back four results, experts or not: the decode
    # chunk's rows of tokens with each step's (L, E) load behind them
    out = eng._decode_fn(eng.params, np.zeros(1, np.int32),
                         np.zeros(1, np.int32), np.zeros((1, 16), np.int32),
                         np.ones(1, np.int32), eng.pool.k_pages,
                         eng.pool.v_pages)
    assert len(out) == 4 and out[0].shape == (E.DECODE_CHUNK * (1 + 2 * 8),)
