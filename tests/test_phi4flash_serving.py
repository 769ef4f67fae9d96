"""Phi-4-mini-flash's layers (Mamba-1 state-space layers, sliding-window and
full differential attention, cross-attention to the one full-length cache,
gated memory units) through ``ServingEngine``, against the plain fp32
reference of ``benchmark/configs/phi4-mini-flash-bf16.py`` — logits, not
tokens — at a small size on the CPU. Every layer kind is kept: 8 layers =
4 (mamba, swa alternating) + the memory layer + the full layer + a gmu + a
cross layer; 64 wide, 4 query heads over 2 K/V heads of 64 (one K/V pair =
one 128-lane row, head-major blocks), window 32, blocks of 16.

* prefill, and prefill-then-decode past the window, against the reference's
  full forward; the benchmark's own probe and scorer over the same engine;
* ``ssm_scan`` in chunks = ``ssm_step`` token by token = a plain loop;
* the three kinds of per-stream state: a stream never holds more than
  window + one block of tokens (and a decode chunk's later write slots) in
  the window pool, freed blocks are used again, admission is atomic over
  blocks, window blocks and the state slot;
* the decode chunk (several steps a dispatch) leaves tokens, window pool,
  conv tails and states as single steps do, across the window's edge;
* recompute preemption and a supervisor's replay give bit-identical tokens;
  concurrent = sequential;
* ``ServingConfig`` refuses prefix cache and speculation for such a model;
  the one-block models keep their configuration keys.
"""
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import fault, telemetry
from mxnet_tpu.ops import ssm
from mxnet_tpu.serving import (EngineSupervisor, KVCacheOOM, ServingConfig,
                               ServingEngine)
from mxnet_tpu.serving import engine as E
from mxnet_tpu.serving import model as M
from mxnet_tpu.serving.kv_cache import (KVBlockPool, PageSpec, StateSlots,
                                        StreamState)
from mxnet_tpu.serving.scheduler import FINISHED, Request

from chunk_cases import chunk_equals_single_steps, lane, tables_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config_module():
    path = os.path.join(ROOT, "benchmark", "configs",
                        "phi4-mini-flash-bf16.py")
    spec = importlib.util.spec_from_file_location("phi4flash_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


C = _config_module()
VOCAB, WINDOW, BS = 211, 32, 16
KINDS = ["mamba", "swa", "mamba", "swa", "mamba", "full", "gmu", "cross"]


def tiny(dtype="float32", **engine):
    """A configuration file's worth of the tiny model."""
    eng = dict(block_size=BS, num_blocks=65, max_batch=4, spec_k=0,
               kv_dtype=dtype,
               prefix_cache=False, prefills_per_step=None)
    eng.update(engine)
    return {
        "model": dict(vocab=VOCAB, num_layers=8, model_dim=64, num_heads=4,
                      num_kv_heads=2, head_dim=64, ffn_dim=128, max_len=256,
                      norm="layer", pos="none", bias=False, layer_kinds=KINDS,
                      window=WINDOW, attn_bias=True, ffn_gated=True,
                      tie_embed=True, ssm_state=16, ssm_conv=4, ssm_expand=2,
                      ssm_dt_rank=4),
        "engine": eng, "weights_dtype": dtype, "init": {"std": 0.2},
        "reference": {"seq_pad": 256, "gen_max": 64, "probe_len": 96,
                      "probe_rows": 8, "probe_decode": [8, 24]}}


@pytest.fixture(scope="module")
def served():
    """(cfg, params, engine, the reference's logits of one 150-token text)"""
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


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.reset()
    yield
    fault.reset()


# ------------------------------------------------------- against the oracle
@pytest.mark.parametrize("n", [5, 16, 40, 100, 150])
def test_prefill_logits_are_the_references(served, n):
    """Shorter than a block, a block exactly, past the window (the window
    layers' first blocks go to trash), and the longest bucket."""
    _cfg, _params, eng, text, want = served
    assert _err(eng.prefill_logits(text[:n]), want[n - 1]) < 1e-4


@pytest.mark.parametrize("n,start", [(40, 20), (100, 20), (150, 10),
                                     (150, 70), (49, 48)])
def test_prefill_then_decode_past_the_window(served, n, start):
    """``start`` tokens through prefill into a scratch stream, the rest one
    by one through the decode program: conv tails, SSM states, the window
    pool (blocks freed as the window slides past 32) and the full pool
    against the reference's one full forward."""
    _cfg, _params, eng, text, want = served
    used = (eng.pool.used(), eng.window_pool.used(), eng.state.used())
    got = eng.prefill_logits(text[:n], decode_from=start)
    assert _err(got, want[n - 1]) < 1e-4
    # the scratch stream's blocks and slot went back
    assert (eng.pool.used(), eng.window_pool.used(),
            eng.state.used()) == used


def test_the_cells_probe_and_scorer_run_over_the_engine(served):
    cfg, params, eng, _text, _want = served
    seen = C.make_probe(cfg)(params, eng.prefill_logits, 7)
    assert seen["rows"] == 8 and seen["worst"] < 1e-4
    assert seen["prefill_quartile"] is not None
    assert seen["decode_quartile"] is not None
    plan = C.probe_plan(cfg, 7)
    decoded = [(n, s) for n, s in plan if s is not None]
    assert len(decoded) == 4
    assert all(s < WINDOW < n and 8 <= n - s <= 24 for n, s in decoded)
    prompt = list(range(1, 45))
    out = eng.generate([prompt], 40)[0]
    off, matches = C.make_reference(cfg)(params, prompt, out)
    assert off == [] and matches >= 38


def test_bfloat16_serving_stays_near_the_reference():
    cfg = tiny("bfloat16")
    cfg["init"] = {"std": 0.113}
    params = C.init_params(cfg, 5)
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=5)
    seen = C.make_probe(cfg)(params, eng.prefill_logits, 5)
    assert seen["quartile"] < 0.05, seen


@pytest.mark.parametrize("wrong", ["window", "lambda", "memory"])
def test_a_wrong_server_is_far_from_the_reference(served, wrong):
    """What the comparison sees at this size in float32: half the window,
    another ``lambda``, the memory layer's ``D x`` left out of ``m``."""
    _cfg, params, _eng, text, want = served
    cfg, bad = tiny(), dict(params)
    if wrong == "window":
        cfg["model"]["window"] = WINDOW // 2
    if wrong == "lambda":       # exp(64 x 0.04) = 13 instead of about 1
        for k in params:
            if k.endswith(("_diff_lambda_q1", "_diff_lambda_k1")):
                bad[k] = jnp.full_like(params[k], 0.2)
    if wrong == "memory":
        bad["layer4_ssm_d"] = jnp.zeros_like(params["layer4_ssm_d"])
    eng = ServingEngine(C.serving_config(cfg), arg_params=bad, seed=3)
    got = eng.prefill_logits(text[:100], decode_from=60)
    assert _err(got, want[99]) > 1e-2


@pytest.mark.parametrize("fault", ["state", "window"])
def test_a_fault_of_the_decode_path_alone_fails_the_probe(served, fault,
                                                          monkeypatch):
    """The state left as prefill made it, or the decode kernel reading half
    the window: the prefilled rows stay sound, the decoded rows do not, and
    ``quartile`` (what ``PROBE_RTOL`` bounds) is the worse half's."""
    cfg, params, _eng, _text, _want = served
    if fault == "state":
        step = M.ssm_step

        def stuck(x, dt, a, b, c, d, z, state, slots, layer):
            y, out, _new = step(x, dt, a, b, c, d, z, state, slots, layer)
            return y, out, state
        monkeypatch.setattr(M, "ssm_step", stuck)
    else:
        paged = M.paged_attention_multi
        monkeypatch.setattr(
            M, "paged_attention_multi", lambda *a, window=None, **kw: paged(
                *a, window=window and window // 2, **kw))
    eng = ServingEngine(C.serving_config(cfg), arg_params=params, seed=3)
    seen = C.make_probe(cfg)(params, eng.prefill_logits, 7)
    assert seen["prefill_quartile"] < 1e-4 < 1e-2 < seen["decode_quartile"]
    assert seen["quartile"] == seen["decode_quartile"]


_TAIL_NEVER_SHIFTED = """
import runpy, sys
import jax.numpy as jnp
from mxnet_tpu.serving.engine import ServingEngine
sound = ServingEngine._dispatch_decode
def faulty(self, *a, **kw):
    kept = jnp.copy(self.state.conv)        # the argument itself is donated
    out = sound(self, *a, **kw)
    self.state.conv = kept
    return out
ServingEngine._dispatch_decode = faulty
sys.argv = sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
"""


def test_the_harness_calls_a_decode_only_fault_not_correct(tmp_path):
    """The rehearsal cell through ``benchmark/run.py`` over an engine that
    throws every decode step's conv tails away (they stay what prefill
    left): the probe's decoded half is far off, its prefilled half sound,
    and the run is ``correct`` false by the probe's limit."""
    import json
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, "-c", _TAIL_NEVER_SHIFTED,
         os.path.join(ROOT, "benchmark", "run.py"), "--rehearsal",
         "--workload", "phi4flash-tiny", "--seed", "5", "--seconds", "2",
         "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    seen = next(l for l in lines if l.get("bench") == "reference")["logits"]
    assert seen["prefill_quartile"] < seen["band"] < seen["decode_quartile"]
    assert seen["quartile"] == seen["decode_quartile"]
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    assert any("first quartile" in l.get("problem", "") for l in lines)


# ------------------------------------------------------------ the recurrence
def test_scan_in_chunks_is_step_by_step_is_a_plain_loop():
    rng = np.random.RandomState(1)
    S, Dn, N = 48, 128, 16
    x, z = rng.randn(2, S, Dn).astype(np.float32)
    dt = rng.randn(S, Dn).astype(np.float32) - 2
    a = -np.exp(0.3 * rng.randn(N, Dn)).astype(np.float32)
    b, c = rng.randn(2, S, N).astype(np.float32)
    d = rng.randn(Dn).astype(np.float32)
    # the plain loop, in float64
    h, ys = np.zeros((N, Dn)), []
    for t in range(S):
        step = np.log1p(np.exp(dt[t].astype(np.float64)))
        h = np.exp(step * a) * h + (step * x[t]) * b[t][:, None]
        ys.append((c[t][:, None] * h).sum(0) + d * x[t])
    ys = np.asarray(ys)
    gated = ys * (z / (1 + np.exp(-z.astype(np.float64))))
    h0 = jnp.zeros((N, Dn), jnp.float32)
    y, out, hT = ssm.ssm_scan(x, dt, a, b, c, d, z, h0, jnp.int32(S))
    np.testing.assert_allclose(y, ys, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(out, gated, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(hT, h, rtol=2e-4, atol=2e-4)
    # two chunks, the state handed over, the second padded past its length
    _y, _o, mid = ssm.ssm_scan(x[:16], dt[:16], a, b[:16], c[:16], d, z[:16],
                               h0, jnp.int32(16))
    pad = np.zeros((16, Dn), np.float32)
    y2, _o2, h2 = ssm.ssm_scan(
        np.concatenate([x[16:], pad]), np.concatenate([dt[16:], pad + 9.0]),
        a, np.concatenate([b[16:], np.ones((16, N), np.float32)]),
        np.concatenate([c[16:], np.ones((16, N), np.float32)]), d,
        np.concatenate([z[16:], pad]), mid, jnp.int32(S - 16))
    np.testing.assert_allclose(y2[:S - 16], ys[16:], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h2, h, rtol=2e-4, atol=2e-4)
    # token by token through slot 2 of layer 1; the other slots stay put
    state = jnp.asarray(rng.randn(2, 4, N, Dn).astype(np.float32))
    state = state.at[1, 2].set(0.0)
    before = np.asarray(state)
    for t in range(S):
        yt, _ot, state = ssm.ssm_step(
            x[t:t + 1], dt[t:t + 1], a, b[t:t + 1], c[t:t + 1], d,
            z[t:t + 1], state, jnp.asarray([2]), 1)
        np.testing.assert_allclose(yt[0], ys[t], rtol=2e-4, atol=2e-4)
    after = np.array(state)
    np.testing.assert_allclose(after[1, 2], h, rtol=2e-4, atol=2e-4)
    after[1, 2] = before[1, 2]
    assert (after == before).all()


# ------------------------------------------------ the three kinds of state
def _window_blocks_a_stream(chunk):
    """Window + one block of tokens, and the write slots a decode chunk
    takes beyond its first: 3 blocks of 16 a step, 4 a chunk of 2 or 4."""
    return -(-(WINDOW + BS + chunk - 1) // BS)


def test_window_blocks_are_freed_behind_the_window_and_used_again(chunk):
    cfg = tiny()
    eng = ServingEngine(C.serving_config(cfg), seed=1)
    per_stream = _window_blocks_a_stream(chunk)
    req = eng.submit(list(range(1, 41)), 150)
    seen, held = set(), 0
    while eng.has_work():
        eng.step()
        live = [b for b in req.wblocks if b]
        seen.update(live)
        held = max(held, len(live))
        # what is held covers the window of the dispatch's first step —
        # which its last step's window has slid past by up to chunk - 1
        # tokens — and nothing behind it
        if req.state == "decoding":
            ctx = req.context_len
            assert all(b == 0 for b in req.wblocks[
                :max(ctx - chunk + 1 - WINDOW, 0) // BS])
            assert all(req.wblocks[max(ctx - WINDOW, 0) // BS:])
    assert req.state == FINISHED and len(req.generated) == 150
    assert (WINDOW + BS) // BS <= held <= per_stream
    st = eng.stats()["state"]
    assert st["window_blocks_a_stream"] == held
    assert st["window_blocks_freed"] >= (40 + 150 - WINDOW) // BS - 1
    # 190 tokens went through 3 blocks at a time: blocks came back and
    # were handed out again (the free list is LIFO)
    assert len(seen) < (40 + 150) // BS
    assert eng.window_pool.used() == 0 and eng.state.used() == 0
    assert eng.pool.used() == 0
    assert st["full_pool_readers"] == 2 and st["full_pool_layers"] == 1
    # the loop's record books the two walks apart: a full-pool reader's is
    # the paged counter's, a window layer's at most the window and a block
    recs = list(eng.obs._ring)
    assert sum(r.lane_steps for r in recs) == 149
    assert sum(r.full_live_blocks for r in recs) \
        == sum(r.live_blocks for r in recs) \
        == eng.stats()["paged"]["live_blocks"]
    assert 149 <= sum(r.window_live_blocks for r in recs) \
        <= 149 * (WINDOW + BS) // BS < sum(r.full_live_blocks for r in recs)
    # the full-length pool's bytes count the other two kinds too
    assert eng.pool.nbytes() == (
        2 * eng.pool.k_pages.size * 4 + eng.window_pool.nbytes()
        + eng.state.nbytes())


def test_a_long_prompt_keeps_only_its_tail_in_the_window_pool():
    cfg = tiny()
    eng = ServingEngine(C.serving_config(cfg), seed=1)
    req = eng.submit(list(range(1, 101)), 20)
    eng.step()
    # 100 cached tokens: the first decode step (context 101) reads from
    # position 69, block 4; blocks 0..3 were never booked (block 6 backs
    # 96..111: the first decode chunk's writes too)
    assert req.wblocks[:4] == [0, 0, 0, 0] and all(req.wblocks[4:])
    assert len([b for b in req.wblocks if b]) == 3 and not req.finished()
    assert len(req.blocks) == 7                              # the full pool
    _drain(eng)
    assert req.state == FINISHED


@pytest.mark.parametrize("kv_heads,rows,head_major", [
    (20, (10, 128), True),      # ten rows do not fill their sublane tiles
    (16, (8, 128), False)])     # eight do: the block stays token-major
def test_a_model_with_kinds_is_served_in_either_block_order(kv_heads, rows,
                                                            head_major):
    """The order of a block is the spec's, and the pools and the step
    programs read it there: a prefill and two decode steps through a full
    and a window pool of either order give the tokens the prefill program
    gives over the whole text."""
    cfg = tiny()
    cfg["model"].update(num_layers=3, layer_kinds=["swa", "full", "cross"],
                        num_heads=kv_heads, num_kv_heads=kv_heads)
    scfg = C.serving_config(cfg)
    full, window = scfg.cache_specs()
    assert full.k_rows == full.v_rows == window.k_rows == rows
    assert full.head_major is window.head_major is head_major
    assert full.block_axis == (3 if head_major else 2)
    eng = ServingEngine(scfg, arg_params=C.init_params(cfg, 3), seed=3)
    assert eng.pool.k_pages.shape == (
        (1, 65, 10, BS, 128) if head_major else (1, 65, BS, 8, 128))
    for pool, spec in ((eng.pool, full), (eng.window_pool, window)):
        assert spec.shape(pool.num_blocks, BS) == (pool.k_pages.shape,
                                                   pool.v_pages.shape)
        assert pool.k_pages.shape[spec.block_axis] == BS
        assert pool.spec == spec
    text = [int(t) for t in
            np.random.RandomState(kv_heads).randint(0, VOCAB, 40)]
    req = eng.submit(text, 3)
    _drain(eng)
    assert req.state == FINISHED and len(req.generated) == 3
    for tok in req.generated:
        assert tok == int(np.argmax(eng.prefill_logits(text)))
        text.append(tok)


def test_admission_is_atomic_over_the_three_kinds():
    """Each kind short in turn: the head waits with NOTHING booked, and is
    admitted once the kind is there."""
    wpool = KVBlockPool(PageSpec.tiled(1, (1, 128)), 4, BS, gauges=False)
    assert wpool.k_pages.shape == (1, 4, 1, BS, 128) and wpool.spec.head_major
    slots = StateSlots(1, 2, 8, (16, 128))
    st = StreamState(wpool, slots, WINDOW)
    a, b = Request([1] * 20, 4), Request([1] * 20, 4)
    assert st.blocks_needed(20) == 2 and st.can_admit(20)
    st.admit(a, 20)
    assert wpool.used() == 2 and slots.used() == 1 and a.slot == 1
    assert not st.can_admit(20)                  # one block, no slot left
    with pytest.raises(KVCacheOOM):
        st.admit(b, 20)
    assert wpool.used() == 2 and b.wblocks == [] and b.slot is None
    st.release(a)
    assert wpool.used() == 0 and slots.used() == 0
    # a slot but too few blocks: the blocks are not taken either
    hog = wpool.alloc(2)
    with pytest.raises(KVCacheOOM):
        st.admit(b, 20)
    assert wpool.used() == 2 and slots.used() == 0 and b.slot is None
    wpool.free(hog)
    st.admit(b, 20)
    assert b.slot is not None and len(b.wblocks) == 2

    # through the scheduler: two state slots left for three requests
    eng = ServingEngine(C.serving_config(tiny()), seed=1)
    hogged = [eng.state.alloc() for _ in range(2)]
    reqs = [eng.submit([1 + i, 2, 3], 20) for i in range(3)]
    eng.step()
    assert [r.state for r in reqs] == ["decoding", "decoding", "waiting"]
    assert reqs[2].blocks == [] and reqs[2].wblocks == [] \
        and reqs[2].slot is None
    assert eng.pool.used() == 2 and eng.state.used() == 4
    _drain(eng)
    assert all(r.state == FINISHED for r in reqs)
    assert eng.state.used() == len(hogged)


def test_a_dry_window_pool_preempts_the_youngest_and_replays_it(chunk):
    """Two streams outgrow the five window blocks left them: the younger is
    preempted (blocks, window blocks and slot returned), replayed through
    prefill, and both streams' tokens are what an unpressed engine gives —
    also with a decode chunk's longer headroom."""
    prompts = [list(range(1, 30)), list(range(40, 69))]
    oracle = ServingEngine(C.serving_config(tiny()), seed=2).generate(
        prompts, 40)
    eng = ServingEngine(C.serving_config(tiny()), seed=2)
    hogged = eng.window_pool.alloc(eng.window_pool.available() - 5)
    reqs = [eng.submit(p, 40) for p in prompts]
    _drain(eng)
    assert [r.state for r in reqs] == [FINISHED] * 2
    assert reqs[1].preemptions >= 1 and reqs[0].preemptions == 0
    assert [list(r.generated) for r in reqs] == oracle
    assert eng.window_pool.used() == len(hogged) and eng.state.used() == 0


def test_chunk_program_equals_single_steps(chunk):
    """The decode chunk over every kind of per-stream state == single
    steps of the same executable: tokens, logits, full pool, window pool,
    conv tails and SSM states, bit for bit — with a stream that crosses
    the window's edge (32) inside the chunk, one that crosses a block
    boundary, and lanes that die by their length cap, their EOS and at
    ``max_len``; a dead lane touches the trash block and slot only."""
    cfg = tiny()
    scfg = C.serving_config(cfg)
    eng = ServingEngine(scfg, seed=3)
    nb = scfg.max_len // BS
    lanes = [lane(5, 30, 9),                  # contexts 31..34: the edge
             lane(7, 61, 2),                  # its length cap; 63 -> 64
             lane(9, 100, 9),                 # its EOS (found below)
             lane(2, scfg.max_len - 2, 9),    # the position cap
             lane(0, 0, 0)]                   # a padded row
    tables = tables_for(lanes, nb, BS)
    wtables = tables_for(lanes, nb, BS)
    slots = np.array([1, 2, 3, 4, 0], np.int32)
    rng = np.random.RandomState(5)
    caches = {k: jnp.asarray(rng.randn(*a.shape), a.dtype) for k, a in dict(
        k=eng.pool.k_pages, v=eng.pool.v_pages, wk=eng.window_pool.k_pages,
        wv=eng.window_pool.v_pages, conv=eng.state.conv,
        ssm=eng.state.ssm).items()}
    aux = ("wk", "wv", "conv", "ssm")

    @jax.jit
    def step(tok, pos, ctx, left, eos, n, c):
        return M.decode_chunk(
            eng.params, tok, pos, tables, ctx, left, eos, n, c["k"], c["v"],
            scfg, chunk, dict({k: c[k] for k in aux}, wtables=wtables,
                              slots=slots))

    def program(tok, pos, ctx, left, eos, n, c):
        rows, logits, kp, vp, out = step(tok, pos, ctx, left, eos,
                                         np.int32(n), c)
        return rows, logits, dict(out, k=kp, v=vp), None

    rows, _ = chunk_equals_single_steps(program, scfg.max_len, lanes, caches,
                                        chunk)
    lanes[2] = lane(9, 100, 9, eos=int(rows[min(1, chunk - 1), 2]))
    rows, _ = chunk_equals_single_steps(program, scfg.max_len, lanes, caches,
                                        chunk)
    assert list((rows >= 0).sum(axis=0)[[0, 1, 3, 4]]) == [
        chunk, min(2, chunk), min(2, chunk), 0]


def test_chunked_serving_is_one_step_a_dispatch(chunk, monkeypatch):
    """Streams that start before the window's edge and end past it, of
    lengths that end inside a chunk: the tokens of an engine that takes
    one step a dispatch, `serving.ssm.stream_steps` and
    `serving.decode_batch` booked for live lanes only, every cache
    returned."""
    rng = np.random.RandomState(8)
    prompts = [list(rng.randint(0, VOCAB, k)) for k in (5, 27, 40, 31)]
    n_new = [30, 11, 21, 6]
    scfg = C.serving_config(tiny())
    monkeypatch.setattr(E, "DECODE_CHUNK", 1)
    want = ServingEngine(scfg, seed=3).generate(prompts, n_new)
    monkeypatch.setattr(E, "DECODE_CHUNK", chunk)
    eng = ServingEngine(scfg, seed=3)
    steps0 = telemetry.counter("serving.ssm.stream_steps").value
    batch0 = telemetry.totals("serving.decode_batch")
    assert eng.generate(prompts, n_new) == want
    lane_steps = sum(n - 1 for n in n_new)
    assert telemetry.counter("serving.ssm.stream_steps").value - steps0 \
        == telemetry.totals("serving.decode_batch")[1] - batch0[1] \
        == lane_steps
    dec = eng.stats()["decode"]
    assert dec["inner_steps"] == -(-29 // chunk) * chunk - (-29 % chunk)
    assert dec["dispatches"] == -(-29 // chunk)
    assert eng.stats()["state"]["window_blocks_a_stream"] \
        <= _window_blocks_a_stream(chunk)
    assert (eng.pool.used(), eng.window_pool.used(), eng.state.used()) \
        == (0, 0, 0)


def test_concurrent_is_sequential():
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, VOCAB, k)) for k in (5, 17, 40, 28)]
    scfg = C.serving_config(tiny())
    together = ServingEngine(scfg, seed=3).generate(prompts, 30)
    alone = ServingEngine(scfg, seed=3)
    assert together == [alone.generate([p], 30)[0] for p in prompts]


def test_supervisor_replay_is_bit_identical(chunk):
    scfg = C.serving_config(tiny())
    prompts = [list(range(1, 30)), [5, 6, 7], list(range(9, 30))]
    oracle = ServingEngine(scfg, seed=6).generate(prompts, 14)
    telemetry.enable()
    sup = EngineSupervisor(lambda: ServingEngine(scfg, seed=6),
                           max_restarts=3, backoff_s=0.02)
    stop = threading.Event()
    # the second decode dispatch fails: 2 to 5 tokens a stream salvaged
    with fault.inject("dispatch_error:raise=1,after=4,times=1"):
        reqs = [sup.submit(p, 14) for p in prompts]
        t = threading.Thread(target=sup.run_loop, args=(stop, 0.01),
                             daemon=True)
        t.start()
        try:
            for r in reqs:
                assert r.done_event.wait(300), (r.rid, r.state)
        finally:
            stop.set()
            with sup.engine._work:
                sup.engine._work.notify_all()
            t.join(timeout=60)
    assert sup.restarts == 1 and sup.failed is None
    assert [list(r.generated) for r in reqs] == oracle
    eng = sup.engine
    assert (eng.pool.used(), eng.window_pool.used(), eng.state.used()) \
        == (0, 0, 0)


# ----------------------------------------------------------- configuration
def test_serving_config_refuses_what_state_cannot_do_yet():
    cfg = tiny()
    with pytest.raises(ValueError, match="state .*at block boundaries"):
        C.serving_config(tiny(prefix_cache=True))
    with pytest.raises(ValueError, match="roll-back"):
        C.serving_config(tiny(spec_k=2))
    scfg = C.serving_config(tiny(prefix_cache=None))
    assert scfg.prefix_cache is False and scfg.stateful and scfg.hybrid
    # sized from max_batch and the decode chunk: streams of window + one
    # block and the chunk's later write slots, and the trash
    eng = ServingEngine(scfg, seed=1)
    assert eng.window_pool.num_blocks == \
        4 * _window_blocks_a_stream(E.DECODE_CHUNK) + 1
    assert eng.state.num_slots == 4 + 1
    model = {k: v for k, v in cfg["model"].items() if k != "vocab"}
    with pytest.raises(ValueError, match="'cross' with no 'full'"):
        ServingConfig(**dict(model, vocab_size=VOCAB,
                             layer_kinds=["mamba"] + ["cross"] * 7))
    with pytest.raises(ValueError, match="layer_kinds"):
        M.ModelConfig(num_kv_heads=2)           # a one-block model has none
    # the programs of a model with kinds have no path for the one block
    with pytest.raises(ValueError, match="layer_kinds must name"):
        ServingConfig(**dict(model, vocab_size=VOCAB,
                             layer_kinds=["attn"] + KINDS[1:]))


def test_one_block_models_keep_their_keys_and_shapes():
    """The programs' cache keys of GPT-2's and OLMoE's block are their
    first fourteen fields, as before ``layer_kinds``; a model that has
    kinds keys on all of them."""
    gpt2 = M.ModelConfig(50257, 24, 1024, 16, 4096, 1024)
    assert gpt2.key() == (50257, 24, 1024, 16, 4096, 1024, "layer",
                          "learned", 10000.0, False, 64, 0, 0, True)
    assert not gpt2.hybrid and not gpt2.stateful
    assert gpt2.kinds() == ("attn",) * 24
    scfg = C.serving_config(tiny())
    # the fields there were before a one-block model's loop_steps
    assert len(scfg.key()) == M.ModelConfig._KIND_FIELDS == 38
    assert scfg.cache_specs().full.k_rows == (1, 128)
    assert scfg.memory_layer == 4
    shapes = M.param_shapes(scfg)
    assert "lm_head_weight" not in shapes and "pos_embed_weight" not in shapes
    assert shapes["layer0_ssm_a_log"] == (16, 128)
    assert shapes["layer1_attn_in_weight"] == (4 * 64 + 2 * 2 * 64, 64)
    assert shapes["layer7_attn_q_weight"] == (256, 64)
    assert "layer7_attn_in_weight" not in shapes
    assert shapes["layer6_gmu_in_weight"] == (128, 64)
    assert shapes["layer0_ffn1_weight"] == (256, 64)
    # the published widths: 3.85 B parameters
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4-mini-flash-bf16.json")) as f:
        full = C.serving_config(json.load(f))
    count = sum(int(np.prod(s)) for s in M.param_shapes(full).values())
    assert 3.84e9 < count < 3.86e9
    assert full.cache_specs().full.k_rows == (10, 128)
    assert PageSpec.tiled(1, (10, 128)).head_major
    assert not PageSpec.tiled(1, (8, 128)).head_major
    assert not PageSpec.tiled(1, (16, 128)).head_major
    assert full.layers_of("mamba") == list(range(0, 17, 2))
    assert full.layers_of("full") == [17] and full.memory_layer == 16
