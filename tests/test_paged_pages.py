"""The lane-dense KV page format on the CPU: ``PageSpec.lane_dense``
puts ``r`` heads side by side in one page row, and everything that touches a
page — the references, the Pallas kernels (interpret mode), the model's
scatter, copy-on-write, the prefix index, the engine — gives what the plain
``(H, D)`` row gives. What the chip's compiler makes of the format is
``tests/test_aot_tpu_compile.py``'s.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import attention as A
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving import model as smodel
from mxnet_tpu.serving.kv_cache import KVBlockPool, PageSpec

# r -> (heads, head_dim) that lane_dense packs r to a row
HEADS = {1: (3, 32), 2: (4, 64), 4: (4, 32)}
# plain (H, D) rows built by hand, one head a row, as the serving engine's
# own suites build them (the cases came from tests/test_serving.py and
# tests/test_serving_spec.py): name -> heads and _rand's sizes
PLAIN = {"plain-2x32": dict(heads=(2, 32), B=4, N=9),
         "plain-2x8-bs4": dict(heads=(2, 8), bs=4, N=16, nb=4)}
DTYPES = {"fp32": (jnp.float32, 1e-6), "bf16": (jnp.bfloat16, 2e-2)}


@pytest.mark.parametrize("heads,head_dim,want", [
    (16, 64, (8, 128)),      # GPT-2 medium: two heads a row
    (12, 64, (6, 128)),
    (4, 32, (1, 128)),       # four heads a row
    (2, 64, (1, 128)),       # the "small" draft preset
    (2, 32, (2, 32)),        # the "tiny" draft preset: 2 % 4 != 0
    (25, 64, (25, 64)),      # GPT-2 XL's odd head count
    (3, 32, (3, 32)),
    (16, 128, (16, 128)),    # already lane-dense
    (8, 256, (8, 256)),
    (4, 48, (4, 48)),        # 128 % 48 != 0
])
def test_page_shape(heads, head_dim, want):
    spec = PageSpec.lane_dense(2, heads, head_dim)
    assert spec.k_rows == spec.v_rows == want and spec.block_axis == 2
    pool = KVBlockPool(spec, 3, 4)
    assert pool.k_pages.shape == (2, 3, 4) + want == pool.v_pages.shape
    assert spec.shape(3, 4) == (pool.k_pages.shape, pool.v_pages.shape)
    assert spec.heads_per_row == heads // want[0]
    assert pool.nbytes() == 2 * pool.k_pages.size * 4


# name -> (full pool, window pool): each the blocks the engine gives the
# pool, then k_pages.shape, v_pages.shape, head_major, parts — read off the
# pools PR 46's engine built for benchmark/configs/<name>.json
PUBLISHED = {
    "gpt2-medium-fp32": (
        (513, (24, 513, 16, 8, 128), (24, 513, 16, 8, 128), False, 1), None),
    "olmoe-1b-7b-bf16": (
        (1025, (8, 1025, 64, 16, 128), (8, 1025, 64, 16, 128), False, 1),
        None),
    "ouro-2.6b-bf16": (
        (145, (48, 580, 32, 16, 128), (48, 580, 32, 16, 128), False, 4),
        None),
    "phi4-mini-flash-bf16": (
        (2561, (1, 2561, 10, 64, 128), (1, 2561, 10, 64, 128), True, 1),
        (641, (8, 641, 10, 64, 128), (8, 641, 10, 64, 128), True, 1)),
    "dots-vlm1-ep16-bf16": (
        (2049, (5, 2049, 1, 128, 512), (5, 2049, 1, 128, 128), True, 1),
        (2, (1, 2, 1, 128, 512), (1, 2, 1, 128, 128), True, 1)),
    "mimo-v2.5-ep16-bf16": (
        (5121, (2, 5121, 4, 64, 256), (2, 5121, 4, 64, 128), True, 1),
        (257, (5, 257, 8, 64, 256), (5, 257, 8, 64, 128), True, 1)),
}


@pytest.mark.parametrize("name", PUBLISHED)
def test_cache_specs_at_published_widths(name):
    """``cache_specs()`` is the one family switch: at each serving
    configuration's published widths it names the pages the engine has
    always built there. Arithmetic only — no array is allocated."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = ServingConfig.from_json(json.load(f))
    assert cfg.num_blocks == PUBLISHED[name][0][0]
    for spec, want in zip(cfg.cache_specs(), PUBLISHED[name]):
        if want is None:
            assert spec is None
            continue
        blocks, k_shape, v_shape, head_major, parts = want
        assert spec.shape(blocks, cfg.block_size) == (k_shape, v_shape)
        assert (spec.head_major, spec.parts) == (head_major, parts)
        assert k_shape[spec.block_axis] == cfg.block_size
        assert spec.block_nbytes(cfg.block_size, 1) * blocks == sum(
            int(np.prod(shape)) for shape in (k_shape, v_shape))


def _rand(r, dtype, lanes, whole_pool, seed=0, B=3, bs=16, N=7, nb=3, L=3,
          heads=None):
    """q (B, T, H, D) with pages packed r heads to a row, as one layer's
    4-D pages or the 5-D pool with a layer index."""
    rng = np.random.RandomState(seed)
    H, D = heads or HEADS[r]
    q = rng.randn(B, lanes, H, D)
    shape = ((L,) if whole_pool else ()) + (N, bs, H // r, D * r)
    kp, vp = rng.randn(*shape), rng.randn(*shape)
    bt = rng.randint(1, N, (B, nb)).astype(np.int32)
    cl = rng.randint(0, nb * bs + 1, (B, lanes)).astype(np.int32)
    cl[0, 0] = 0                       # an empty lane reads exact zeros
    cl[-1, -1] = nb * bs               # and a full one reads every slot
    q, kp, vp = (jnp.asarray(x, dtype) for x in (q, kp, vp))
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(cl), (1 if whole_pool
                                                         else None)


def _unpacked(pages, r):
    H, D = HEADS[r]
    return pages.reshape(pages.shape[:-2] + (H, D))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("r", HEADS)
def test_packed_reference_is_the_unpacked_reference(r, dtype):
    """Packing is a reshape: bit for bit, single- and multi-query, one
    layer's pages and the whole pool."""
    for whole_pool in (False, True):
        q, kp, vp, bt, cl, layer = _rand(r, DTYPES[dtype][0], 3, whole_pool)
        got = A.paged_attention_multi_reference(q, kp, vp, bt, cl,
                                                layer=layer)
        want = A.paged_attention_multi_reference(
            q, _unpacked(kp, r), _unpacked(vp, r), bt, cl, layer=layer)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        one = A.paged_attention_reference(q[:, 0], kp, vp, bt, cl[:, 0],
                                          layer=layer)
        want = A.paged_attention_reference(
            q[:, 0], _unpacked(kp, r), _unpacked(vp, r), bt, cl[:, 0],
            layer=layer)
        np.testing.assert_array_equal(np.asarray(one, np.float32),
                                      np.asarray(want, np.float32))
        assert not np.asarray(one[0], np.float32).any()


# ---- what a loop over a stream's own blocks can get wrong (PR 27). Tables of
# seven slots over blocks of 16, fetched two blocks at a time: contexts in
# tokens per stream (decode) or per stream and lane (verify).
_BS, _NB, _C = 16, 7, 2
_FULL = _NB * _BS
RAGGED = {
    # nothing, one token, a block, a block and one, a fetch, a fetch and
    # one, the whole table — in one batch
    "boundaries": [[0], [1], [_BS], [_BS + 1], [_C * _BS], [_C * _BS + 1],
                   [_FULL]],
    # the prefetch across streams: the next stream's first fetch is
    # started from the last fetch of this one, short or long
    "last-longest": [[1], [_BS + 1], [_FULL]],
    "first-longest": [[_FULL], [_BS], [1]],
    # a padded batch row as the engine builds it: context 1, every table
    # entry the trash block
    "padded-row": [[3 * _BS + 2], [1], [_C * _BS + 5]],
    # the loop is bounded by the longest lane, wherever it stands
    "lanes": [[5, _FULL - 3, 0, _BS, _C * _BS + 1],
              [_C * _BS, _C * _BS + 1, _C * _BS + 2, 1, 2],
              [0, 0, 0, 0, 0],
              [_FULL, 1, 1, 1, 1]],
    # table entries past the context name a block of 1e30s: never read
    "dead-1e30": [[_BS + 3], [_C * _BS], [1]],
    # a context past the table (a verify window at max_len) stops at it
    "past-the-table": [[_FULL + 9], [_FULL + _BS + 1], [2]],
}
ALIGNED = (16, 64)     # two heads a row fill (8, 128): no padding at the edge


def _ragged(case, r, dtype, whole_pool, heads=None, N=12, L=2):
    H, D = heads or HEADS[r]
    rng = np.random.RandomState(len(case) + r)
    cl = np.asarray(RAGGED[case], np.int32)
    B, lanes = cl.shape
    q = rng.randn(B, lanes, H, D)
    shape = ((L,) if whole_pool else ()) + (N, _BS, H // r, D * r)
    kp, vp = rng.randn(*shape), rng.randn(*shape)
    bt = rng.randint(1, N - 1, (B, _NB)).astype(np.int32)
    if case == "padded-row":
        bt[1] = 0
    if case == "dead-1e30":
        kp[..., N - 1, :, :, :] = vp[..., N - 1, :, :, :] = 1e30
        for i in range(B):
            bt[i, -(-int(cl[i].max()) // _BS):] = N - 1
    q, kp, vp = (jnp.asarray(x, dtype) for x in (q, kp, vp))
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(cl), (1 if whole_pool
                                                         else None)


_KERNEL_CASES = [
    ("random", r, dt, lanes, pool)
    for r in HEADS for dt in DTYPES for lanes in (None, 3)
    for pool in (False, True)
] + [
    # every r with either page form and every dtype with either
    (case, r, dt, None, (dt == "bf16") != (r == 2))
    for case in RAGGED for r in HEADS for dt in DTYPES
] + [("boundaries", "aligned", "fp32", None, True),
     ("lanes", "aligned", "bf16", None, False),
     ("random", "plain-2x32", "fp32", None, False),
     ("random", "plain-2x8-bs4", "fp32", 3, False)]


@pytest.mark.parametrize(
    "case,r,dtype,lanes,whole_pool", _KERNEL_CASES,
    ids=["-".join([c, r if r in PLAIN else "r%s" % r, dt,
                   "decode" if n is None and c != "lanes" else "verify",
                   "5d-layer" if w else "4d"])
         for c, r, dt, n, w in _KERNEL_CASES])
def test_pallas_interpret_matches_reference(case, r, dtype, lanes, whole_pool,
                                            monkeypatch):
    """The kernel program the TPU runs, interpreted on the CPU."""
    dt, tol = DTYPES[dtype]
    if r in PLAIN:
        q, kp, vp, bt, cl, layer = _rand(1, dt, lanes or 1, whole_pool,
                                         seed=len(r), **PLAIN[r])
    elif case == "random":
        q, kp, vp, bt, cl, layer = _rand(r, dt, lanes or 1, whole_pool,
                                         seed=r)
    else:
        heads, r = (ALIGNED, 2) if r == "aligned" else (None, r)
        q, kp, vp, bt, cl, layer = _ragged(case, r, dt, whole_pool, heads)
        lanes = None if cl.shape[1] == 1 else cl.shape[1]
        # two blocks a fetch at these small pages, as the benchmark's pages
        # take eight (or two) of the table's 64
        rows = kp.shape[-3:]
        monkeypatch.setattr(A, "_PAGED_FETCH_BYTES", _C * 64 * 1024)
        assert A._paged_blocks_per_fetch(*rows, dt, _NB) == _C
    scale = 1.0 / np.sqrt(q.shape[-1])
    if lanes is None:
        q, cl = q[:, 0], cl[:, 0]
        got = A._paged_pallas(q, kp, vp, bt, cl, scale, layer=layer,
                              interpret=True)
        want = A.paged_attention_reference(q, kp, vp, bt, cl, layer=layer)
    else:
        got = A._paged_pallas_multi(q, kp, vp, bt, cl, scale, layer=layer,
                                    interpret=True)
        want = A.paged_attention_multi_reference(q, kp, vp, bt, cl,
                                                 layer=layer)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    if case in ("random", "boundaries"):
        assert not np.asarray(got[0], np.float32).reshape(
            -1, q.shape[-1])[:q.shape[-2]].any(), "empty lane must read zeros"


def test_pages_must_hold_the_heads():
    q, kp, vp, bt, cl, _ = _rand(2, jnp.float32, 1, False)
    with pytest.raises(ValueError, match="cannot hold"):
        A.paged_attention_multi(q[:, :, :3], kp, vp, bt, cl)
    with pytest.raises(ValueError, match="layer="):
        A._paged_pallas_multi(q, kp[None], vp[None], bt, cl, 0.125)


def _lm(r):
    H, D = HEADS[r]
    return dict(vocab_size=29, num_layers=2, model_dim=H * D, num_heads=H,
                ffn_dim=48, max_len=64)


def _greedy(cfg, params, prompt, n, pages):
    """Prompt + n greedy tokens through ``model.prefill`` / ``decode`` over
    the given pool pages, one stream on blocks 1.."""
    bs = pages[0].shape[2]
    nb = cfg.max_len // bs
    table = np.arange(1, nb + 1, dtype=np.int32)
    S = -(-len(prompt) // bs) * bs
    toks = np.zeros((1, S), np.int32)
    toks[0, :len(prompt)] = prompt
    nxt, logits, kp, vp = smodel.prefill(
        params, toks, np.int32(len(prompt)), table[:S // bs], *pages, cfg)
    out, all_logits = [int(nxt[0])], [np.asarray(logits[0])]
    for t in range(len(prompt), len(prompt) + n - 1):
        nxt, logits, kp, vp = smodel.decode(
            params, np.array(out[-1:], np.int32), np.array([t], np.int32),
            table[None], np.array([t + 1], np.int32), kp, vp, cfg)
        out.append(int(nxt[0]))
        all_logits.append(np.asarray(logits[0]))
    return out, np.stack(all_logits)


@pytest.mark.parametrize("r", HEADS)
def test_model_reads_the_format_off_its_pages(r):
    """``prefill`` and ``decode`` over a pool from ``lane_dense`` and over
    pages built ``(H, D)`` by hand: the same logits bit for bit."""
    cfg = smodel.ModelConfig(**_lm(r))
    H, D = HEADS[r]
    params = smodel.as_device_params(smodel.random_params(cfg, seed=5), cfg)
    prompt = list(np.random.RandomState(r).randint(0, cfg.vocab_size, 11))
    packed = KVBlockPool(cfg.cache_specs().full, 9, 8)
    assert packed.spec.heads_per_row == r
    plain = jnp.zeros((cfg.num_layers, 9, 8, H, D), jnp.float32)
    got, got_logits = _greedy(cfg, params, prompt, 6,
                              (packed.k_pages, packed.v_pages))
    want, want_logits = _greedy(cfg, params, prompt, 6, (plain, plain))
    assert got == want
    np.testing.assert_array_equal(got_logits, want_logits)


@pytest.mark.parametrize("r", HEADS)
def test_engine_serves_and_reports_the_format(r):
    """An engine over each format — r = 1 here is an odd head count — emits
    the tokens the model emits over hand-built pages, through a prefix hit
    and a copy-on-write, and says which format it runs."""
    H, D = HEADS[r]
    cfg = ServingConfig(block_size=8, num_blocks=32, max_batch=4,
                        prefills_per_step=1, prefix_cache=True, **_lm(r))
    eng = ServingEngine(cfg, seed=5)
    st = eng.stats()
    assert st["kv_heads_per_row"] == r
    assert st["kv_page_shape"] == list(cfg.cache_specs().full.k_rows)
    assert st["kv_page_shape"] == ([H // r, 128] if r > 1 else [H, D])
    shared = list(range(1, 17))                 # two full blocks
    prompts = [shared + tail for tail in ([], [17], [18, 19])]
    # twenty tokens: a stream outlives its first dispatch's decode chunk,
    # so its blocks are still indexed when the next prompt is admitted
    reqs = [eng.submit(p, 20) for p in prompts]
    while any(not q.finished() for q in reqs):
        eng.step()
    assert eng.pool.prefix_stats()["hits"] >= 2
    plain = jnp.zeros((cfg.num_layers, 9, 8, H, D), jnp.float32)
    for p, q in zip(prompts, reqs):
        assert list(q.generated) == _greedy(cfg, eng.params, p, 20,
                                            (plain, plain))[0]
    assert eng.pool.used() == 0


@pytest.mark.parametrize("r", [2, 4])
def test_cow_and_prefix_hit_on_a_packed_pool(r):
    H, D = HEADS[r]
    pool = KVBlockPool(PageSpec.lane_dense(2, H, D), 9, 4)
    (b,) = pool.alloc(1)
    rng = np.random.RandomState(0)
    kv = rng.randn(2, 4, H, D).astype(np.float32)
    rows = kv.reshape((2, 4) + pool.spec.k_rows)
    pool.k_pages = pool.k_pages.at[:, b].set(rows)
    pool.v_pages = pool.v_pages.at[:, b].set(2.0 * rows)
    tokens = [5, 6, 7, 8, 9]
    assert pool.prefix_insert(tokens, [b]) == 1
    assert pool.prefix_match(tokens) == [b] and pool.refcount(b) == 2
    nb = pool.cow(b)
    assert nb != b and pool.refcount(b) == pool.refcount(nb) == 1
    for blk in (b, nb):       # a block's bytes are the (H, D) rows' bytes
        np.testing.assert_array_equal(
            np.asarray(pool.k_pages[:, blk]).reshape(kv.shape), kv)
        np.testing.assert_array_equal(
            np.asarray(pool.v_pages[:, blk]).reshape(kv.shape), 2.0 * kv)


# ---- head-major pages, a window, grouped query lanes (PR 31): ten rows of
# 128 lanes as (G, bs, W) slabs; the kernel in interpret mode against the
# gathered reference, and the reference against a dense computation.
def _head_major(case, dtype, g=10, w=128, N=12, L=2, lanes=4):
    cl = np.asarray(RAGGED[case], np.int32)
    # one context a stream for all its lanes, or the case's own per lane
    cl = cl[:, :lanes] if cl.shape[1] > 1 else np.repeat(cl, lanes, axis=1)
    rng = np.random.RandomState(len(case))
    B = len(cl)
    q = rng.randn(B, lanes, g, w)
    kp, vp = rng.randn(2, L, N, g, _BS, w)
    bt = rng.randint(1, N - 1, (B, _NB)).astype(np.int32)
    if case == "dead-1e30":
        kp[:, N - 1] = vp[:, N - 1] = 1e30
        for i in range(B):
            bt[i, -(-int(cl[i].max()) // _BS):] = N - 1
    q, kp, vp = (jnp.asarray(x, dtype) for x in (q, kp, vp))
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(cl)


# four lanes that share a context (the four query heads of a differential
# K/V row); since PR 41 also one lane and three (a T that is no tile), lanes
# whose contexts differ inside a stream ("lanes": an empty lane beside a
# long one, contexts that end on a block's last slot, a whole stream empty)
# and a window whose start falls inside a block (24 of blocks of 16)
_HM_CASES = [(4, case, window)
             for case in ("boundaries", "last-longest", "first-longest",
                          "dead-1e30")
             for window in (None, 40, 16)]
_HM_CASES += [(lanes, case, window) for lanes in (1, 3)
              for case in ("boundaries", "lanes", "dead-1e30")
              for window in (None, 24)]
_HM_CASES += [(4, "lanes", window) for window in (None, 40, 16, 24)]
_HM_CASES += [(4, "boundaries", 24)]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize(
    "lanes,case,window", _HM_CASES,
    ids=["T%d-%s-%s" % (t, c, "full" if w is None else "w%d" % w)
         for t, c, w in _HM_CASES])
def test_head_major_kernel_is_the_reference(lanes, case, window, dtype):
    """The same walk over ``(G, bs, W)`` blocks, a block's arithmetic on
    the MXU: T query lanes a stream, from the block that holds the shortest
    lane's ``context - window``; slots behind the window are never read."""
    dt, tol = DTYPES[dtype]
    q, kp, vp, bt, cl = _head_major(case, dt, lanes=lanes)
    if window is not None and case == "dead-1e30":
        # blocks wholly behind every lane's window may name anything too
        bt = np.array(bt)
        for i in range(len(bt)):
            bt[i, :max(int(cl[i].min()) - window, 0) // _BS] = kp.shape[1] - 1
        bt = jnp.asarray(bt)
    want = A.paged_attention_multi_reference(
        q, kp, vp, bt, cl, sm_scale=0.125, layer=1, window=window,
        head_major=True)
    got = A._paged_pallas_multi(q, kp, vp, bt, cl, 0.125, layer=1,
                                interpret=True, window=window,
                                head_major=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol * 4,
                               rtol=tol * 4)
    assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("window", [None, 24])
def test_head_major_reference_is_dense_attention(window):
    """The oracle's own oracle: token-major pages of the same numbers, and
    a dense masked softmax over the gathered keys."""
    q, kp, vp, bt, cl = _head_major("boundaries", jnp.float32, g=3, lanes=2)
    got = A.paged_attention_multi_reference(
        q, kp, vp, bt, cl, sm_scale=0.1, layer=0, window=window,
        head_major=True)
    flat = A.paged_attention_multi_reference(
        q, kp.transpose(0, 1, 3, 2, 4), vp.transpose(0, 1, 3, 2, 4), bt, cl,
        sm_scale=0.1, layer=0, window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(flat))
    k = np.asarray(kp[0])[np.asarray(bt)].transpose(0, 1, 3, 2, 4).reshape(
        len(bt), -1, 3, 128)
    v = np.asarray(vp[0])[np.asarray(bt)].transpose(0, 1, 3, 2, 4).reshape(
        len(bt), -1, 3, 128)
    for b in range(len(bt)):
        ctx = int(cl[b, 0])
        lo = max(ctx - window, 0) if window else 0
        if ctx == 0:
            assert not np.asarray(got[b]).any()
            continue
        s = np.einsum("thd,khd->thk", np.asarray(q[b]), k[b, lo:ctx]) * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(got[b]), np.einsum("thk,khd->thd", p, v[b, lo:ctx]),
            atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [20, 64])
def test_token_major_kernel_takes_a_window_too(window):
    q, kp, vp, bt, cl, layer = _ragged("lanes", 2, jnp.float32, True,
                                       heads=ALIGNED)
    want = A.paged_attention_multi_reference(q, kp, vp, bt, cl, layer=layer,
                                             window=window)
    got = A._paged_pallas_multi(q, kp, vp, bt, cl, 0.125, layer=layer,
                                interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6,
                               rtol=2e-6)


@pytest.mark.parametrize("window", [None, 100, 1])
def test_flash_forward_window_is_the_masked_softmax(window):
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 3, 256, 64), jnp.float32)
               for _ in range(3))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * 0.125
    i, j = np.arange(256)[:, None], np.arange(256)[None]
    seen = (j <= i) & ((i - j < window) if window else True)
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
    got = A.flash_attention(q, k, v, True, None, 64, window)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    kern, _lse = A._pallas_forward(q, k, v, True, 0.125, block_q=64,
                                   block_k=128, interpret=True,
                                   window=window)
    np.testing.assert_allclose(np.asarray(kern), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dn", [128, 1024])
def test_ssm_kernels_are_their_xla_paths(dn):
    """``ops/ssm.py``'s two Pallas kernels in interpret mode against the
    ``lax.scan`` / gather-scatter lowerings the CPU runs."""
    from mxnet_tpu.ops import ssm

    rng = np.random.RandomState(dn)
    S, N, B = 128, 16, 5
    x, z = rng.randn(2, S, dn).astype(np.float32)
    dt = rng.randn(S, dn).astype(np.float32) - 2
    a = -np.exp(0.3 * rng.randn(N, dn)).astype(np.float32)
    b, c = rng.randn(2, S, N).astype(np.float32)
    d = rng.randn(dn).astype(np.float32)
    h0 = rng.randn(N, dn).astype(np.float32)
    for length in (S, 77, 1):
        want = ssm.ssm_scan_reference(x, dt, a, b, c, d, z, h0,
                                      jnp.int32(length))
        got = ssm._scan_pallas(x, dt, a, b, c, d, z, h0, jnp.int32(length),
                               interpret=True)
        for g, w in zip(got[:2], want[:2]):          # y, out: the live rows
            np.testing.assert_allclose(np.asarray(g)[:length],
                                       np.asarray(w)[:length], atol=1e-4,
                                       rtol=1e-4)
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                                   atol=1e-4, rtol=1e-4)
    state = jnp.asarray(rng.randn(2, 7, N, dn).astype(np.float32))
    slots = jnp.asarray([3, 6, 0, 1, 0])        # two padded rows: the trash
    want = ssm.ssm_step_reference(x[:B], dt[:B], a, b[:B], c[:B], d, z[:B],
                                  state, slots, 1)
    got = ssm._step_pallas(x[:B], dt[:B], a, b[:B], c[:B], d, z[:B], state,
                           slots, 1, interpret=True)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=1e-5)
    g, w = np.asarray(got[2]), np.asarray(want[2])
    np.testing.assert_allclose(g[:, 1:], w[:, 1:], atol=1e-5, rtol=1e-5)
    assert (g[0] == np.asarray(state)[0]).all()         # the other layer
    assert (g[1, [2, 4, 5]] == np.asarray(state)[1, [2, 4, 5]]).all()


# ------------------------------------------------------ the latent format
def _latent_case(case, dtype, seed=0, B=4, H=8, C=128, R=128, bs=16, N=12,
                 nb=4, L=2):
    """Absorbed queries over a latent pool ``(L, N, 1, bs, C)`` /
    ``(L, N, 1, bs, R)``; the rotary part fills 8 of R's lanes."""
    rng = np.random.RandomState(seed)
    qc, qr = rng.randn(B, H, C), np.zeros((B, H, R))
    qr[..., :8] = rng.randn(B, H, 8)
    cp, rp = rng.randn(L, N, 1, bs, C), np.zeros((L, N, 1, bs, R))
    rp[..., :8] = rng.randn(L, N, 1, bs, 8)
    bt = rng.randint(1, N, (B, nb)).astype(np.int32)
    cl = {"boundaries": [1, bs, bs + 1, nb * bs],
          "last-longest": [3, 17, 30, nb * bs],
          "first-longest": [nb * bs, 40, 2, 1],
          "padded-rows": [33, 1, 1, 1]}[case]
    cast = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    return (cast(qc), cast(qr), cast(cp), cast(rp), jnp.asarray(bt),
            jnp.asarray(np.asarray(cl, np.int32)))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", ["boundaries", "last-longest",
                                  "first-longest", "padded-rows"])
def test_latent_kernel_is_the_reference(case, dtype):
    """``latent_paged``'s Pallas kernel (interpret mode) against its XLA
    path: streams that end on and just past a block boundary, the longest
    first and last (the prefetch hands over between streams), padded rows
    of one token; the whole pool with a layer index."""
    dt, tol = DTYPES[dtype]
    args = _latent_case(case, dt)
    want = A.latent_paged_reference(*args, sm_scale=0.2, layer=1)
    got = A._latent_pallas(*args, sm_scale=0.2, layer=1, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=max(tol, 2e-5) * 4, rtol=tol)
    # one layer's 4-D pages: the same rows
    one = A._latent_pallas(args[0], args[1], args[2][1], args[3][1],
                           *args[4:], sm_scale=0.2, interpret=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got))


def test_latent_reference_is_dense_attention_over_the_cached_rows():
    qc, qr, cp, rp, bt, cl = _latent_case("last-longest", jnp.float32)
    got = np.asarray(A.latent_paged(qc, qr, cp, rp, bt, cl, 0.2, layer=0))
    for i in range(qc.shape[0]):
        rows = np.asarray(cp[0])[np.asarray(bt[i])].reshape(-1, 128)
        keys = np.asarray(rp[0])[np.asarray(bt[i])].reshape(-1, 128)
        n = int(cl[i])
        s = (np.asarray(qc[i]) @ rows[:n].T
             + np.asarray(qr[i]) @ keys[:n].T) * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:n]
        np.testing.assert_allclose(got[i], want, atol=2e-5)


def test_latent_pool_format():
    """``k_pages`` one ``kv_rank``-wide row a token, ``v_pages`` the rotary
    key's 128-lane row, both head-major (a block is the ``(bs, W)`` slab);
    the bytes are the two arrays'."""
    cfg = smodel.ModelConfig(
        97, 5, 64, 8, 32, 256, norm="rms", pos="rope", bias=False,
        head_dim=128, layer_kinds=["mla"] * 5, q_rank=48, kv_rank=512,
        rope_dim=64, v_dim=128)
    spec = cfg.cache_specs().full
    assert spec == PageSpec(5, (1, 512), (1, 128), True)
    assert spec.block_axis == 3
    pool = KVBlockPool(spec, 9, 16, dtype=jnp.bfloat16, prefix_cache=False)
    assert pool.k_pages.shape == (5, 9, 1, 16, 512)
    assert pool.v_pages.shape == (5, 9, 1, 16, 128)
    assert spec.shape(9, 16) == (pool.k_pages.shape, pool.v_pages.shape)
    assert pool.spec is spec
    assert pool.nbytes() == pool.k_pages.nbytes + pool.v_pages.nbytes \
        == 5 * 9 * 16 * 640 * 2
    assert pool.block_nbytes() == 5 * 16 * 640 * 2
    # the window stand-in of a model without "swa" layers: one layer
    assert cfg.cache_specs().window == PageSpec(1, (1, 512), (1, 128), True)
    # every other pool keeps two arrays of one shape and its old bytes
    plain = KVBlockPool(PageSpec.lane_dense(2, 16, 64), 3, 4)
    assert plain.spec.v_rows == plain.spec.k_rows == (8, 128)
    assert plain.nbytes() == 2 * plain.k_pages.size * 4


@pytest.mark.parametrize("path", ["scan", "pallas"])
def test_flash_forward_takes_a_key_width_other_than_its_value_width(path):
    """Latent attention's expanded heads: keys 192 wide (128 + 64 rotary),
    values 128: causal softmax(q k^T) v, by both lowerings."""
    rng = np.random.RandomState(3)
    q, k = (jnp.asarray(rng.randn(1, 2, 256, 192) * 0.3, jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, 2, 256, 128), jnp.float32)
    if path == "scan":
        got = A.flash_attention(q, k, v, True, 0.1)
    else:
        got, _lse = A._pallas_forward(q, k, v, True, 0.1, interpret=True)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) * 0.1
    s = np.where(np.tril(np.ones((256, 256), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True),
                     np.asarray(v))
    assert got.shape == (1, 2, 256, 128)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
