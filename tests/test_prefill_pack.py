"""The packed prefill: a step's prompts end to end along the rows of ONE
program of the ladder, each from a block boundary and attending to itself
alone (``serving/model.py`` ``prefill``'s ``length`` as a pack; the flash
forward's ``first_key``, ``ops/attention.py``; ``ServingEngine._cut_packs``).

    python3 -m pytest tests/test_prefill_pack.py -q

The kinds that pack — every model with experts and no state slots — at
the rehearsal's tiny widths in float32: latent attention with a share of
its experts, plain grouped-query attention ("full" and "swa" with a sink)
over two pools, routed experts over a plain block. A model with state slots
and a model without experts (the plain GPT-2 block, the looped stack) take
one prompt a program, its bare length: the program they always traced
(``model._one_prompt`` says why).

The digests of ``test_a_prompt_alone_traces_the_program_there_was`` are of
the programs the PARENT of the PR that brought packs traced (its commit
93b3702): run this file as a script to print them anew after a change to
the programs that is meant (``python3 tests/test_prefill_pack.py``).
"""
import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.ops import attention as A  # noqa: E402
from mxnet_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from mxnet_tpu.serving import engine as E  # noqa: E402
from mxnet_tpu.serving import model as M  # noqa: E402
from mxnet_tpu.serving.obs import loop_records  # noqa: E402
from mxnet_tpu.serving.scheduler import Request  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "rehearsal", "configs")
PACKING = {"mla": "dotsvlm1-tiny", "gqa": "mimo-tiny", "experts": "olmoe-tiny"}
ALONE = {"plain": "lm-tiny", "looped": "ouro-tiny", "mamba": "phi4flash-tiny",
         "kda": "solar-tiny"}
KINDS = sorted(PACKING)
#: three prompts of unequal lengths: 1, 2 and 3 blocks of 16 slots in the
#: cache, 8 + 24 + 40 rows of the rung of 128 (starts 0, 8, 32: the second
#: and the third inside a block)
LENGTHS = (5, 19, 33)


def config(name, **engine):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        obj = json.load(f)
    obj["engine"].update(kv_dtype="float32", **engine)
    return ServingConfig.from_json(obj)


def prompts(lengths, seed=1, vocab=250):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(1, vocab, n)] for n in lengths]


def aligned(lengths):
    """The rows each prompt takes of a program: whole ``PACK_ALIGN``s."""
    return [-(-n // M.PACK_ALIGN) * M.PACK_ALIGN for n in lengths]


def rung_of(cfg, lengths):
    """The smallest rung that holds prompts of ``lengths`` end to end."""
    return E._bucket_for(sum(aligned(lengths)), cfg.prefill_buckets())


def lanes_of(eng, texts):
    """A scratch stream a text: blocks (and window blocks) booked."""
    lanes = [Request(list(t), 1) for t in texts]
    for req in lanes:
        req.blocks = eng.pool.alloc(eng.pool.blocks_for(len(req.prompt)))
        if eng.streams is not None:
            eng.streams.admit(req, len(req.prompt))
    return lanes


def run_pack(eng, lanes):
    """The lanes' prompts through ONE prefill program; ``(tokens (n,),
    logits (n, V), load or None)`` of the pack's n prompts."""
    pack = [(req, req.prompt) for req in lanes]
    toks, spans, table, wtable = eng._lay_pack(pack, rung_of(
        eng.config, [len(req.prompt) for req in lanes]))
    tok, logits = eng._dispatch_prefill(toks, spans, table, wtable,
                                        lanes[0].slot or 0)
    width = M.pack_width(eng.config, toks.shape[1])
    tok, load = E._unpack_fetch(np.asarray(tok), (width,), eng.config)
    return (tok[:len(lanes)], np.asarray(logits, np.float32)[:len(lanes)],
            load)


def blocks_of(eng, req):
    """What the full pool (and the window pool) hold of a stream's prompt:
    its tokens' slots, block by block (a slot behind the prompt's end holds
    whatever row the program had there)."""
    bs, n = eng.config.block_size, len(req.prompt)
    held = []
    for pool, table in ((eng.pool, req.blocks),
                        (eng.window_pool, req.wblocks or [])):
        if pool is None:
            continue
        for pages in (pool.k_pages, pool.v_pages):
            pages = np.asarray(pages)
            for i, block in enumerate(table[:-(-n // bs)]):
                if not block:       # behind the window: never written
                    continue
                slots = pages[:, block]
                if pool.spec.head_major:        # (layers, G, bs, W)
                    slots = slots.transpose(0, 2, 1, 3)
                held.append(slots[:, :min(bs, n - i * bs)])
    return held


# ------------------------------------------------------------ the program --
@pytest.fixture(scope="module", params=KINDS)
def packed(request):
    """One engine a kind and what one pack of three left in it, beside the
    same three prompts each prefilled alone (a pack of one) in another
    engine of the same weights."""
    cfg = config(PACKING[request.param])
    texts = prompts(LENGTHS)
    together, alone = ServingEngine(cfg, seed=3), ServingEngine(cfg, seed=3)
    lanes = lanes_of(together, texts)
    lone = lanes_of(alone, texts)
    return {"cfg": cfg, "eng": together, "lanes": lanes,
            "pack": run_pack(together, lanes),
            "held": [blocks_of(together, req) for req in lanes],
            "alone": [run_pack(alone, [req]) for req in lone],
            "alone_held": [blocks_of(alone, req) for req in lone]}


def test_a_pack_gives_each_prompt_what_it_gets_alone(packed):
    """Logits, greedy token, the pages' blocks and the experts' loads."""
    tok, logits, load = packed["pack"]
    assert logits.shape == (3, packed["cfg"].vocab_size)
    for i, (tok1, logits1, _load1) in enumerate(packed["alone"]):
        np.testing.assert_allclose(logits[i], logits1[0], rtol=2e-4,
                                   atol=2e-5)
        assert tok[i] == tok1[0] == int(np.argmax(logits1[0]))
        for got, want in zip(packed["held"][i], packed["alone_held"][i]):
            assert got.shape == want.shape and got.size
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    if packed["cfg"].num_experts:
        # every pair counted, to the pair: the pack's valid rows and no other
        want = sum(load1 for _t, _l, load1 in packed["alone"])
        np.testing.assert_array_equal(load, want)
        assert load.sum() == (sum(LENGTHS) * packed["cfg"].experts_per_tok
                              * packed["cfg"].expert_layers)
    else:
        assert load is None


@pytest.mark.parametrize("which", [0, 1, 2])
def test_a_prompt_in_a_pack_never_reads_a_neighbour(packed, which):
    """Other tokens in one prompt move that prompt's logits and nobody
    else's, to the bit: rows of a matmul do not mix and the floor keeps the
    prompts apart."""
    eng, lanes = packed["eng"], packed["lanes"]
    texts = [list(req.prompt) for req in lanes]
    texts[which] = [(t + 7) % 250 + 1 for t in texts[which]]
    moved = [Request(t, 1) for t in texts]
    for req, was in zip(moved, lanes):
        req.blocks, req.wblocks, req.slot = was.blocks, was.wblocks, was.slot
    _tok, logits, _load = run_pack(eng, moved)
    for i in range(3):
        same = np.array_equal(logits[i], packed["pack"][1][i])
        assert same == (i != which), i


def _digest(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(r"/[^ :\"']*\.py", "FILE", text)
    text = re.sub(r":\d+", ":N", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _prefill_shapes(cfg, length):
    """``model.prefill``'s arguments as shapes, in the rung of two blocks;
    ``length``: the shape of that argument."""
    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    bs, dt = cfg.block_size, cfg.kv_dtype
    S = 2 * bs
    params = jax.eval_shape(
        lambda: M.as_device_params(M.random_params(cfg), cfg))
    full, win = cfg.cache_specs()
    kshape, vshape = full.shape(cfg.num_blocks, bs)
    args = [params, s((1, S)), s(length), s((S // bs,)), s(kshape, dt),
            s(vshape, dt)]
    if not cfg.hybrid:
        return args, None
    wk, wv = win.shape(9, bs)
    conv, state = cfg.slot_shapes()
    n = max(len(cfg.layers_of("mamba", "kda")), 1)
    return args, dict(wtable=s((S // bs,)), slot=s(()), wk=s(wk, dt),
                      wv=s(wv, dt), conv=s((n, 3, conv), jnp.float32),
                      ssm=s((n, 3) + tuple(state), jnp.float32))


def _prefill_digest(name, length=()):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = ServingConfig.from_json(json.load(f))
    args, aux = _prefill_shapes(cfg, length)
    return _digest(lambda a, aux: M.prefill(*a, cfg, aux), args, aux)


def _kernel_digests():
    q = jnp.zeros((1, 4, 256, 64), jnp.float32)
    k = jnp.zeros((1, 2, 256, 64), jnp.float32)
    return {
        "flash": _digest(lambda q: A.flash_attention(q, q, q, True), q),
        "flash_window": _digest(
            lambda q: A.flash_attention(q, q, q, True, 0.1, 256, 128), q),
        "flash_gqa": _digest(lambda q, k: A.flash_attention_gqa(
            q, k, k, 0.1, 128, jnp.ones(4)), q, k),
        "flash_grad": _digest(jax.grad(
            lambda q: A.flash_attention(q, q, q, True).sum()), q),
    }


#: the parent's programs (93b3702), by `_digest`; this file as a script
#: prints them
PARENT = {
    "dotsvlm1-tiny": "448dcf90b9b82edd",
    "flash": "7be45e0e4da675c7",
    "flash_gqa": "a70aeb3bfb6c9547",
    "flash_grad": "7d1bef3aa05ea940",
    "flash_window": "32cd28e78ef5f72d",
    "lm-tiny": "26f6017176f61b0e",
    "mimo-tiny": "67cab3b475431422",
    "olmoe-tiny": "3cd1d2ae54a1263b",
    "ouro-tiny": "f8e1de844f53558b",
    "phi4flash-tiny": "8054210e35a6e0b6",
    "solar-tiny": "190a40fd028f9c33",
}


@pytest.mark.parametrize("name", sorted(set(PACKING.values())
                                        | set(ALONE.values())))
def test_a_prompt_alone_traces_the_program_there_was(name):
    """``length`` () — one prompt from row 0, what a model with state slots
    or without experts is always handed — traces the parent's jaxpr to the
    character, every family's: no floor, no table lookup of positions,
    nothing of a pack. (The engine hands a model that packs the pack's form
    for a lone prompt too: for the three families of ``PACKING`` this holds
    ``model.prefill``'s bare-length form, which tests and tools call, not
    what their cells run.)"""
    assert _prefill_digest(name) == PARENT[name]


@pytest.mark.parametrize("name", ["flash", "flash_window", "flash_gqa",
                                  "flash_grad"])
def test_no_floor_traces_the_kernel_there_was(name):
    """``first_key=None``: the flash forward (training's, its gradient, a
    window's, grouped queries' with a sink) is the parent's program."""
    assert _kernel_digests()[name] == PARENT[name]


@pytest.mark.parametrize("kind", sorted(ALONE))
def test_a_model_that_takes_one_prompt_is_never_packed(kind):
    """State slots, or no experts: one prompt a program whatever the rung,
    the prompt's bare length its argument, and the engine's packs are of
    one though its rungs are warm; a model with experts and no such layers
    packs by its program's shape alone."""
    cfg = config(ALONE[kind])
    bs = cfg.block_size
    assert [M.pack_width(cfg, S) for S in cfg.prefill_buckets()] \
        == [1] * len(cfg.prefill_buckets())
    assert M.pack_blocks(cfg, 4 * bs, bs) == 4
    length = M.pack_of(cfg, 64, [(0, 19)])
    assert np.ndim(length) == 0 and length == 19 and length.dtype == np.int32
    with pytest.raises(ValueError):
        M.pack_of(cfg, 64, [(0, 19), (32, 5)])
    rungs = cfg.prefill_buckets()       # every one warm: still alone
    assert [i - j for j, i, _rung in E.cut_packs(
        aligned((5, 19, 7)), rungs, rungs, M.pack_width(cfg, rungs[-1]))] \
        == [1, 1, 1]
    # and its program, handed a prompt alone, is the parent's
    assert _prefill_digest(ALONE[kind]) == PARENT[ALONE[kind]]
    other = config("olmoe-tiny")
    assert [M.pack_width(other, S) for S in (16, 32, 128, 256)] \
        == [2, 4, 16, M.PACK_MAX]
    # a prompt more is up to a block more in the cache than along the rows
    assert [M.pack_blocks(other, S, 16) for S in (16, 32, 128)] == [2, 5, 23]
    pack = M.pack_of(other, 32, [(0, 19), (24, 5)])
    np.testing.assert_array_equal(pack, [[0, 24, 32, 32], [19, 5, 0, 0]])


# ------------------------------------------------------------- the kernel --
def _floored(seq, starts):
    floor = np.zeros(seq, np.int32)
    for s in starts:
        floor[s:] = s
    return jnp.asarray(floor)


@pytest.mark.parametrize("window", [None, 96], ids=["full", "w96"])
@pytest.mark.parametrize("path", ["pallas", "scan", "flash", "gqa"])
def test_the_floor_against_the_reference(path, window):
    """A floor a query row against ``attention_reference``'s, with and
    without a window; the starts 40 and 700 fall inside a KV block (of 256
    rows, and of 128), 512 on one; grouped queries by the index map."""
    rng = np.random.RandomState(0)
    seq, group = 1024, 2
    q = jnp.asarray(rng.randn(1, 4, seq, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 4 // group, seq, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 4 // group, seq, 32), jnp.float32)
    floor = _floored(seq, (0, 40, 512, 700))
    wide = [jnp.repeat(t, group, axis=1) for t in (k, v)]
    want = A.attention_reference(q, *wide, True, 0.125, window=window,
                                 first_key=floor)
    if path == "pallas":
        got, _lse = A._pallas_forward(
            q, k, v, True, 0.125, block_q=256, interpret=True, window=window,
            kv_group=group, first_key=floor)
    elif path == "scan":
        got, _lse = A._scan_forward(q, *wide, True, 0.125, 128, window,
                                    floor)
    elif path == "flash":
        got = jax.jit(lambda q, k, v, f: A.flash_attention(
            q, k, v, True, 0.125, 256, window, f))(q, *wide, floor)
    else:
        got = A.flash_attention_gqa(q, k, v, 0.125, window, None, 256, floor)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the floor is not nothing: without it the later rows read further back
    loose = A.attention_reference(q, *wide, True, 0.125, window=window)
    assert float(jnp.abs(loose - want)[:, :, 700:].max()) > 1e-2


def test_a_floor_is_forward_only():
    q = jnp.ones((1, 2, 128, 16), jnp.float32)
    floor = _floored(128, (0, 64))
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda q: A.flash_attention(
            q, q, q, True, None, 256, None, floor).sum())(q)


# -------------------------------------------------------------- the engine --
def tiny_config(**kw):
    fields = dict(vocab_size=61, num_layers=1, model_dim=32, num_heads=2,
                  ffn_dim=48, max_len=128, block_size=16, num_blocks=65,
                  max_batch=16, kv_dtype=np.float32, num_experts=4,
                  experts_per_tok=2)
    fields.update(kw)
    return ServingConfig(**fields)


def tiny_engine(**kw):
    return ServingEngine(tiny_config(**kw), seed=3)


LADDER = (16, 32, 64, 128)


@pytest.mark.parametrize("lengths,warm,engine,want", [
    # two prompts fill the top rung's rows: the third stands alone
    ((60, 60, 10), LADDER, {}, [[60, 60], [10]]),
    # neighbours only, in plan order: 24 + 96 rows fill the rung of 128
    ((100, 30, 20, 90), LADDER, {}, [[100], [30], [20, 90]]),
    # a lone prompt is a pack of one, in its own rung
    ((20,), LADDER, {}, [[20]]),
    # sixteen prompts close a pack whatever rows are left
    ((3,) * 17, (128,), {"block_size": 4}, [[3], [3] * 16]),
    # no rung has run: a prompt alone compiles its own, a pack nothing
    ((5, 19, 33), (), {}, [[5], [19], [33]]),
    # a pack runs only in a rung that has run: 8 + 24 rows in the rung of
    # 64 would cost more rows than 16 + 32 alone, 24 + 40 there fewer
    ((5, 19, 33, 7), (64,), {}, [[5], [19, 33], [7]]),
    ((5, 19, 33, 7), (16, 128), {}, [[5, 19, 33, 7]]),
    # rows decide: 72 + 56 and 40 + 40 + 32 rows, two rungs of 128, where
    # five programs would compute 352 rows; among equals the fewest programs
    ((70, 50, 40, 33, 30), LADDER, {}, [[70, 50], [40, 33, 30]]),
    ((9, 9, 17), LADDER, {}, [[9, 9, 17]]),
], ids=["rows", "order", "lone", "sixteen", "cold", "warm64", "warm128",
        "fewest_rows", "fewest_programs"])
def test_the_steps_prompts_are_cut_into_the_fewest_rows(lengths, warm, engine,
                                                        want):
    """The engine's own cut over an engine whose rungs ``warm`` are
    compiled (``warmup(prefill_buckets=)``) and no other."""
    eng = tiny_engine(**engine)
    if warm:
        eng.warmup(prefill_buckets=warm)
    reqs = [Request(p, 1) for p in prompts(lengths, vocab=60)]
    packs = eng._cut_packs(reqs)
    assert [[len(t) for _r, t in pack] for pack, _rung in packs] == want
    assert [r for pack, _rung in packs for r, _t in pack] == reqs
    for pack, rung in packs:      # a pack of several compiles nothing
        assert sum(aligned(len(t) for _r, t in pack)) <= rung
        assert len(pack) == 1 or rung in warm
        assert rung in eng.config.prefill_buckets()


@pytest.mark.parametrize("seed", range(6))
def test_no_cut_into_neighbours_computes_fewer_rows(seed):
    """Against every way to cut up to nine prompts into runs of
    neighbours, each run of several in the smallest warm rung that holds
    it."""
    import itertools

    rng = np.random.RandomState(seed)
    lengths = [int(n) for n in rng.randint(1, 100, rng.randint(2, 10))]
    warm = sorted(rng.choice(LADDER, rng.randint(1, 5), replace=False))
    warm = [int(b) for b in warm]
    found = E.cut_packs(aligned(lengths), LADDER, warm,
                        M.pack_width(tiny_config(), warm[-1]))
    assert [j for j, _i, _rung in found] \
        == [0] + [i for _j, i, _rung in found[:-1]]

    def rows_of(run):
        rows = sum(-(-n // 8) * 8 for n in run)
        rungs = LADDER if len(run) == 1 else warm
        return next((b for b in rungs if rows <= b), None)

    best = None
    for cuts in itertools.product((0, 1), repeat=len(lengths) - 1):
        runs, run = [], [lengths[0]]
        for n, cut in zip(lengths[1:], cuts):
            if cut:
                runs.append(run)
                run = []
            run.append(n)
        rungs = [rows_of(r) for r in runs + [run]]
        if None not in rungs:
            best = min(best or (1 << 30, 0), (sum(rungs), len(rungs)))
    assert (sum(rung for _j, _i, rung in found), len(found)) == best


def _closed_greedily(need, buckets, most):
    """The cut ISSUE 51 asked for: a pack closes when the next prompt's rows
    would pass the top rung or ``most``; the rows its programs compute."""
    rows, runs, run = 0, [], []
    for n in need:
        if run and (sum(run) + n > buckets[-1] or len(run) == most):
            runs.append(run)
            run = []
        run.append(n)
    return sum(E._bucket_for(sum(r), buckets) for r in runs + [run])


@pytest.mark.parametrize("group,padded,over", [
    (2.0, 15.0, 0.03), (4.66, 11.0, 0.05), (8.0, 9.0, 0.05), (60.0, 7.0, 0.05),
], ids=["g2", "g4.66", "g8", "ramp60"])
def test_the_fewest_rows_against_packs_closed_greedily(group, padded, over):
    """Why the cut is not the greedy one: over the lengths of
    ``dotsvlm1-chat-closed256`` (lognormal, median 512, sigma 0.8, 64 to
    2,048; its ladder of 128 to 3,072 rows, every rung warm) in groups of
    ``group`` prompts a step (4.66: ledger, PR 50; 60 and more in the ramp),
    both from multiples of ``PACK_ALIGN``: the fewest-rows cut never computes
    more rows than the greedy one, computes ``over`` fewer in all, and pads
    under ``padded`` per cent (a prompt alone in its own rung: 22). The
    chip agreed with this arithmetic twice (PERF.md section 6, PR 51:
    block-aligned greedy 20.5 measured, 19.5 here; this cut 8.6 measured,
    9.4 here)."""
    ladder = (128, 256, 512, 1024, 1536, 2048, 3072)
    rng = np.random.RandomState(51)
    tokens = fewest = greedy = 0
    for _ in range(300 if group < 20 else 40):
        lengths = np.clip(np.exp(rng.normal(
            np.log(512), 0.8, max(1, rng.poisson(group)))), 64, 2048)
        need = aligned(int(n) for n in lengths)
        runs = E.cut_packs(need, ladder, ladder, M.PACK_MAX)
        a, b = (sum(rung for _j, _i, rung in runs),
                _closed_greedily(need, ladder, M.PACK_MAX))
        assert a <= b
        tokens, fewest, greedy = (tokens + int(sum(lengths)), fewest + a,
                                  greedy + b)
    assert 100 * (1 - tokens / fewest) < padded
    assert fewest < (1 - over) * greedy


def _forced_to_one(monkeypatch):
    monkeypatch.setattr(M, "pack_width", lambda cfg, rows: 1)


@pytest.mark.parametrize("kind", KINDS)
def test_generate_is_token_for_token_the_prompts_one_by_one(kind,
                                                            monkeypatch):
    """Sixteen prompts admitted in one step, packed, against the same
    engine with every pack forced to one."""
    cfg = config(PACKING[kind], max_batch=16, num_blocks=129)
    texts = prompts((5, 19, 33, 7, 16, 40, 3, 21, 9, 30, 17, 2, 48, 11, 26,
                     14))
    eng = ServingEngine(cfg, seed=3)
    eng.warmup()
    got = eng.generate(texts, 5)
    stats = eng.stats()["prefill"]
    assert stats["prompts"] == 16 and stats["groups"] == 1
    assert stats["programs"] < 16
    assert stats["prompts_per_program"] == 16 / stats["programs"] > 1.5
    _forced_to_one(monkeypatch)
    one = ServingEngine(cfg, seed=3)
    assert one.generate(texts, 5) == got
    assert one.stats()["prefill"]["prompts_per_program"] == 1.0


def test_the_counters_say_how_often_prompts_share_a_program():
    """``prefill_programs`` on the step's record, ``prefill_rows`` once a
    PROGRAM, ``stats()["prefill"]`` and the histogram of a pack's prompts."""
    eng = tiny_engine()
    eng.warmup(prefill_buckets=[128])
    before = telemetry.totals("serving.prefill.pack")
    rows0 = telemetry.counter("serving.prefill_rows").value
    t0 = __import__("time").time()
    for p in prompts((60, 60, 10), vocab=60):
        eng.submit(p, 2)
    eng.step()
    stats = eng.stats()
    assert stats["prefill"]["programs"] == 2
    assert stats["prefill"]["prompts_per_program"] == 1.5
    (rec,) = [r for r in loop_records(t0) if r.prefills]
    assert (rec.prefills, rec.prefill_programs) == (3, 2)
    # 120 rows of two prompts in the rung of 128, 10 in the rung of 16
    assert rec.prefill_rows == 128 + 16 and rec.prefill_tokens == 130
    assert stats["loop"]["sums"]["prefill_programs"] == 2
    assert telemetry.counter("serving.prefill_rows").value - rows0 == 144
    after = telemetry.totals("serving.prefill.pack")
    assert (after[0] - before[0], after[1] - before[1]) == (2, 3)
    assert "serving.prefill.pack" in telemetry.METRIC_HELP


def test_a_preempted_requests_replay_rides_in_a_pack(monkeypatch):
    """A stream preempted while decoding is prefilled again — prompt and
    the tokens it had made — beside a fresh prompt, in one program; both
    end with the tokens they make one by one."""
    texts = prompts((20, 9, 14), vocab=60)

    def run():
        eng = tiny_engine()
        eng.warmup()
        first = eng.submit(texts[0], 40)
        eng.step()
        assert first.state == E.DECODING and len(first.generated) > 2
        with eng._lock:
            eng.scheduler._preempt(first)
        assert first.pending_token is not None
        others = [eng.submit(t, 6) for t in texts[1:]]
        programs = eng.stats()["prefill"]["programs"]
        while not all(r.finished() for r in [first] + others):
            eng.step()
        return ([list(r.generated) for r in [first] + others],
                eng.stats()["prefill"]["programs"] - programs,
                first.preemptions)

    got, programs, preempted = run()
    assert preempted == 1 and programs == 1     # replay and two prompts
    _forced_to_one(monkeypatch)
    want, programs, _ = run()
    assert programs == 3 and got == want


def test_shared_prefix_blocks_stay_unwritten_inside_a_pack():
    """A prompt that starts from the prefix index's blocks, packed behind
    another: its shared blocks' write entries go to the trash block, the
    blocks keep what they held to the bit, and its tokens are those of an
    engine without the index."""
    head = prompts((32,), seed=5, vocab=60)[0]
    texts = [prompts((9,), seed=6, vocab=60)[0],
             head + prompts((13,), seed=7, vocab=60)[0]]

    def run(prefix_cache):
        eng = tiny_engine(prefix_cache=prefix_cache)
        eng.warmup()
        holder = eng.submit(head + [1, 2, 3], 60)  # holds the head's blocks
        eng.step()
        assert holder.state == E.DECODING
        reqs = [eng.submit(t, 6) for t in texts]
        shared, held = [], None
        with eng._lock:
            plan = eng.scheduler.schedule()
            assert plan.prefills == reqs
            shared = reqs[1].blocks[:reqs[1].shared_blocks]
            if shared:
                held = np.asarray(eng.pool.k_pages)[:, shared]
            eng._run_prefills(plan.prefills)
            if shared:
                np.testing.assert_array_equal(
                    np.asarray(eng.pool.k_pages)[:, shared], held)
        assert eng.stats()["prefill"]["programs"] == 2     # one pack of two
        eng._gap_after = None
        while not all(r.finished() for r in reqs):
            eng.step()
        return [list(r.generated) for r in reqs], len(shared)

    got, shared = run(True)
    want, none = run(False)
    assert (shared, none) == (2, 0) and got == want


# ------------------------------------------------------- the planted fault --
def _wrong_servers():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import wrong_servers
    finally:
        sys.path.pop(0)
    return wrong_servers


@pytest.mark.parametrize("kind", KINDS)
def test_a_pack_that_sees_its_neighbours_is_not_the_prompt_alone(kind):
    """``tools/wrong_servers.py``'s server whose packed prompts see their
    neighbours (the floor dropped): the first prompt of a pack is sound,
    every later one is off by far more than any band."""
    cfg = config(PACKING[kind])
    texts = prompts(LENGTHS)
    sound = ServingEngine(cfg, seed=3)
    _tok, want, _load = run_pack(sound, lanes_of(sound, texts))
    with _wrong_servers().planted("pack_sees_neighbours", {}):
        wrong = ServingEngine(cfg, seed=3)
        _tok, got, _load = run_pack(wrong, lanes_of(wrong, texts))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-5)
    for i in (1, 2):
        assert np.abs(got[i] - want[i]).max() > 0.05 * scale, i


@pytest.mark.parametrize("kind", KINDS)
def test_a_served_step_of_the_planted_server_makes_wrong_tokens(kind):
    """The fault through ``submit`` and ``step``, cut as a step cuts (no
    probe's own): every request that stood FIRST in its pack makes the
    sound server's tokens, and requests behind another make other tokens,
    in every kind that packs."""
    cfg = config(PACKING[kind], max_batch=8, num_blocks=129)
    texts = prompts((20, 18, 30, 25, 12, 28, 9, 40), seed=11)

    def served(eng):
        eng.warmup()
        reqs = [eng.submit(t, 8) for t in texts]
        with eng._lock:     # the cut the step is about to make
            firsts = {id(pack[0][0]) for pack, _S in eng._cut_packs(reqs)}
        while not all(r.finished() for r in reqs):
            eng.step()
        assert eng.stats()["prefill"]["groups"] == 1
        return ([list(r.generated) for r in reqs],
                [id(r) in firsts for r in reqs])

    want, first = served(ServingEngine(cfg, seed=3))
    with _wrong_servers().planted("pack_sees_neighbours", {}):
        got, first_too = served(ServingEngine(cfg, seed=3))
    assert first == first_too and 0 < sum(first) < len(texts) / 2
    wrong = [g != w for g, w in zip(got, want)]
    assert not any(w for w, f in zip(wrong, first) if f), (wrong, first)
    assert sum(wrong) >= 2, (wrong, first)


@pytest.mark.parametrize("name", ["olmoe-tiny"])
def test_the_cells_scorer_refuses_a_prompt_that_saw_its_neighbours(name):
    """OLMoE's cell's ``correct`` re-scores served
    requests against the configuration's plain reference
    (``drivers/serve.py`` ``judge``, the module's ``make_reference``): the
    same scorer passes every request of a sound pack and refuses requests
    that stood behind another in the planted server's."""
    ws = _wrong_servers()
    cfg, mod = ws.load_config(os.path.join(CONFIGS, name + ".json"))
    cfg["engine"]["max_batch"] = 8
    # (the chat cell's module has no `serving_config`: `drivers/serve.py`
    # makes the engine's from the file's two objects, as `from_json` does)
    scfg = mod.serving_config(cfg) if hasattr(mod, "serving_config") \
        else ServingConfig.from_json(cfg)
    params = mod.init_params(cfg, 5)
    score = mod.make_reference(cfg)
    texts = prompts((20, 18, 30, 25, 12, 28), seed=11)

    def refused(eng):
        eng.warmup()
        reqs = [eng.submit(t, 12) for t in texts]
        while not all(r.finished() for r in reqs):
            eng.step()
        assert eng.stats()["prefill"]["programs"] < len(texts)
        return [bool(score(params, t, list(r.generated))[0])
                for t, r in zip(texts, reqs)]

    assert refused(ServingEngine(scfg, arg_params=params, seed=5)) \
        == [False] * 6
    with ws.planted("pack_sees_neighbours", cfg["model"]):
        wrong = refused(ServingEngine(scfg, arg_params=params, seed=5))
    assert not wrong[0] and sum(wrong[1:]) >= 2, wrong


@pytest.mark.parametrize("cell,seed", [("dotsvlm1-tiny", 5)])
def test_the_harness_calls_a_pack_that_sees_its_neighbours_not_correct(
        cell, seed, tmp_path):
    """The rehearsal cell through ``benchmark/run.py`` over the planted
    server: the probe's decoded half prefills its lanes' texts as a step
    does, cut into packs as a step cuts them, so on a seed whose texts
    share programs (this one) its quartile is far over the band and the
    run is ``correct`` false by the probe's limit, whatever the window's
    packs held; the prefilled half (a text alone) is sound. On a seed whose
    texts each stand first in their program the probe is blind, as a
    prompt first in its pack is sound (mimo-tiny at seeds 3 and 5): every
    packing cell's ``correct`` sees this fault by luck (PERF.md section 7),
    and ``test_a_served_step_of_the_planted_server_makes_wrong_tokens`` is
    the guard that does not rest on a seed."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "wrong_servers.py"),
         "--cell", cell, "--rehearsal", "--faults", "pack_sees_neighbours",
         "--seeds", str(seed)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    seen = next(l for l in lines if l.get("bench") == "reference")["logits"]
    assert seen["prefill_quartile"] < seen["band"] < seen["decode_quartile"]
    assert lines[-1] == {"fault": "pack_sees_neighbours", "seed": seed,
                         "cell": cell, "through": "benchmark/run.py",
                         "correct": False}
    assert any("first quartile" in l.get("problem", "") for l in lines)


if __name__ == "__main__":
    for _name in sorted(PARENT):
        if _name.startswith("flash"):
            print('    "%s": "%s",' % (_name, _kernel_digests()[_name]))
        else:
            print('    "%s": "%s",' % (_name, _prefill_digest(_name)))
