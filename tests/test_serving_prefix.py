"""Prefix-sharing KV cache suite (docs/serving.md §prefix-sharing):
refcount/copy-on-write pool invariants, the chained content-digest prefix
index, eviction-gain victim picking, and the engine-level contracts —
logits/token parity with sharing on, concurrency multiplication at a
fixed pool size, and preemption invisibility with shared blocks in play.

Host-side only: part of tier-1 (tests/conftest.py pins jax to the CPU);
``ci/run_tests.sh serving`` runs the serving files alone.
"""
import importlib
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.serving import (  # noqa: E402
    KVBlockPool, KVCacheOOM, Request, Scheduler, ServingConfig, ServingEngine)
from mxnet_tpu.serving import model as smodel  # noqa: E402
from mxnet_tpu.serving.kv_cache import PageSpec  # noqa: E402

pytestmark = pytest.mark.serving

tlm = importlib.import_module("mxnet_tpu.models.transformer_lm")

CFG = dict(vocab_size=23, num_layers=2, model_dim=32, num_heads=2,
           ffn_dim=48, max_len=64)
SEED = 3


def _config(**over):
    kw = dict(CFG, block_size=8, num_blocks=64, max_batch=8,
              prefills_per_step=4)
    kw.update(over)
    return ServingConfig(**kw)


def _pool(**over):
    kw = dict(num_layers=1, num_blocks=9, block_size=4, num_heads=2,
              head_dim=8)
    kw.update(over)
    spec = PageSpec.lane_dense(kw.pop("num_layers"), kw.pop("num_heads"),
                               kw.pop("head_dim"))
    return KVBlockPool(spec, kw.pop("num_blocks"), kw.pop("block_size"), **kw)


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
# pool refcounts + copy-on-write
# ---------------------------------------------------------------------------


def test_refcount_lifecycle_and_shared_free():
    pool = _pool()
    blocks = pool.alloc(3)
    assert all(pool.refcount(b) == 1 for b in blocks)
    pool.incref([blocks[0]])
    assert pool.refcount(blocks[0]) == 2
    # freeing the shared block once reclaims NOTHING; the sole-owner
    # blocks return to the free list
    released = pool.free(blocks)
    assert released == 2
    assert pool.refcount(blocks[0]) == 1
    assert pool.used() == 1
    # the second holder's free releases it — exactly once
    assert pool.free([blocks[0]]) == 1
    assert pool.used() == 0
    assert pool.available() == pool.num_usable


def test_double_free_of_shared_block_is_hard_error():
    pool = _pool()
    (b,) = pool.alloc(1)
    pool.incref([b])
    pool.free([b])
    pool.free([b])   # refcount 0: block back on the free list
    with pytest.raises(ValueError, match="double free"):
        pool.free([b])
    # accounting survived the rejected free
    assert pool.available() == pool.num_usable


def test_trash_block_never_refcounted_shared_or_indexed():
    pool = _pool()
    with pytest.raises(ValueError):
        pool.free([0])
    with pytest.raises(ValueError, match="incref"):
        pool.incref([0])
    with pytest.raises(ValueError):
        pool.cow(0)
    assert pool.refcount(0) == 0
    # prefix machinery never touches block 0 either: a full pool's index
    # contains only allocated non-trash blocks by construction
    blocks = pool.alloc(2)
    pool.prefix_insert(list(range(2 * pool.block_size)), blocks)
    assert 0 not in pool._block_digest


def test_incref_of_free_block_rejected():
    pool = _pool()
    (b,) = pool.alloc(1)
    pool.free([b])
    with pytest.raises(ValueError, match="incref"):
        pool.incref([b])


def test_cow_sole_owner_is_identity():
    pool = _pool()
    (b,) = pool.alloc(1)
    assert pool.cow(b) == b
    assert pool.used() == 1


def test_cow_shared_block_copies_pages_bit_exactly():
    pool = _pool()
    (b,) = pool.alloc(1)
    rng = np.random.RandomState(0)
    assert pool.spec.shape(9, 4) == ((1, 9, 4, 2, 8),) * 2
    kv = rng.randn(*pool.k_pages.shape[:1] + pool.k_pages.shape[2:]).astype(
        pool.dtype)
    pool.k_pages = pool.k_pages.at[:, b].set(kv)
    pool.v_pages = pool.v_pages.at[:, b].set(2.0 * kv)
    pool.incref([b])
    nb = pool.cow(b)
    assert nb != b
    assert pool.refcount(b) == 1 and pool.refcount(nb) == 1
    np.testing.assert_array_equal(np.asarray(pool.k_pages[:, nb]), kv)
    np.testing.assert_array_equal(np.asarray(pool.v_pages[:, nb]), 2.0 * kv)
    # the original holder's data is untouched
    np.testing.assert_array_equal(np.asarray(pool.k_pages[:, b]), kv)
    assert pool.cow_copies == 1


def test_cow_with_dry_free_list_raises_oom():
    pool = _pool()
    blocks = pool.alloc(pool.num_usable)
    pool.incref([blocks[0]])
    with pytest.raises(KVCacheOOM):
        pool.cow(blocks[0])


def test_refcount_zero_exactly_once_under_interleavings():
    """Three holders acquire/release one shared block in every arrival
    order: the block returns to the free list exactly once, and a fourth
    release is a hard error — under admit/preempt/finish-style
    interleavings the accounting can neither leak nor double-release."""
    import itertools

    for order in itertools.permutations(range(3)):
        pool = _pool()
        (b,) = pool.alloc(1)           # holder 0 allocates
        pool.incref([b])               # holder 1 maps the shared prefix
        pool.incref([b])               # holder 2 maps the shared prefix
        released = []
        for _h in order:
            released.append(pool.free([b]))
        assert released.count(1) == 1 and released.count(0) == 2, \
            "block must hit the free list exactly once (order %s)" % (order,)
        assert pool.available() == pool.num_usable
        with pytest.raises(ValueError, match="double free"):
            pool.free([b])


def test_pool_invariant_counts_shared_blocks_once():
    pool = _pool()
    blocks = pool.alloc(4)
    pool.incref(blocks)   # every block shared by two holders
    # free + referenced must equal usable (shared blocks counted ONCE)
    assert pool.available() + pool.used() == pool.num_usable
    assert pool.used() == 4
    pool.free(blocks)
    pool.free(blocks)
    assert pool.used() == 0


# ---------------------------------------------------------------------------
# the prefix index
# ---------------------------------------------------------------------------


def test_prefix_match_insert_roundtrip_and_refcounts():
    pool = _pool()
    bs = pool.block_size
    tokens = list(range(1, 2 * bs + 3))   # two full blocks + partial tail
    blocks = pool.alloc(3)
    assert pool.prefix_insert(tokens, blocks) == 2, \
        "only FULL blocks are indexable"
    got = pool.prefix_match(tokens)
    assert got == blocks[:2]
    assert pool.refcount(blocks[0]) == 2 and pool.refcount(blocks[1]) == 2
    # a prefix equal in the first block only matches one block
    other = tokens[:bs] + [9] * bs
    assert pool.prefix_match(other) == blocks[:1]
    # completely different tokens: no match, lookup still counted
    assert pool.prefix_match([7] * (2 * bs)) == []
    stats = pool.prefix_stats()
    assert stats["lookups"] == 3 and stats["hits"] == 2
    assert stats["hit_blocks"] == 3


def test_prefix_index_dropped_when_last_reference_released():
    pool = _pool()
    bs = pool.block_size
    tokens = list(range(bs))
    blocks = pool.alloc(1)
    pool.prefix_insert(tokens, blocks)
    held = pool.prefix_match(tokens)
    assert held == blocks
    pool.free(blocks)                       # original holder leaves
    assert pool.prefix_match(tokens) == held  # survives: matcher holds it
    pool.free(held)                         # first matcher's grant
    pool.free(held)                         # second matcher's grant: rc 0
    assert pool.prefix_match(tokens) == [], \
        "index entry must die with the block's last reference"


def test_prefix_digests_are_position_sensitive():
    """Same token block content at a DIFFERENT block ordinal must never
    match: cached K/V bakes in absolute position embeddings."""
    pool = _pool()
    bs = pool.block_size
    x, y = [1] * bs, [2] * bs
    blocks = pool.alloc(2)
    pool.prefix_insert(x + y, blocks)
    # y as block 0 (position base 0) must not hit y's block-1 entry
    assert pool.prefix_match(y + x) == []
    # x+y matches both, x + wrong-tail matches the first only
    assert pool.prefix_match(x + [3] * bs) == blocks[:1]
    pool.free(blocks[:1])  # release the probe's grants
    m = pool.prefix_match(x + y)
    assert m == blocks
    assert pool.prefix_stats()["index_size"] == 2


def test_prefix_insert_first_writer_wins():
    pool = _pool()
    bs = pool.block_size
    tokens = list(range(bs))
    b1 = pool.alloc(1)
    b2 = pool.alloc(1)
    assert pool.prefix_insert(tokens, b1) == 1
    assert pool.prefix_insert(tokens, b2) == 0, \
        "an already-indexed digest must keep its first block"
    assert pool.prefix_match(tokens) == b1


def test_prefix_cache_disabled_is_inert():
    pool = _pool(prefix_cache=False)
    bs = pool.block_size
    tokens = list(range(bs))
    blocks = pool.alloc(1)
    assert pool.prefix_insert(tokens, blocks) == 0
    assert pool.prefix_match(tokens) == []
    assert pool.prefix_stats()["enabled"] is False
    assert pool.prefix_stats()["lookups"] == 0


# ---------------------------------------------------------------------------
# eviction gain (satellite: victim picker uses refcounts)
# ---------------------------------------------------------------------------


def test_zero_gain_stream_never_picked_as_victim():
    """A stream whose blocks are ALL shared frees nothing when evicted —
    the victim picker must skip it (scanning youngest-first) and land on
    the youngest stream with actual reclaim gain."""
    from mxnet_tpu.serving.scheduler import DECODING

    pool = _pool(num_blocks=17)
    sched = Scheduler(pool, max_batch=8)
    old = Request([1], 4)
    young = Request([1], 4)
    old.blocks = pool.alloc(2)
    young.blocks = pool.alloc(2)
    pool.incref(young.blocks)      # every young block shared elsewhere
    for r in (old, young):
        r.state = DECODING
        r.pending_token = 1
    sched.running = [old, young]
    assert pool.reclaimable(young.blocks) == 0
    assert sched._pick_victim(ensuring=old) is old, \
        "zero-gain stream must be skipped"
    # ensuring the zero-gain stream itself: nothing at-or-after it frees
    # blocks, and FCFS forbids reaching the older stream -> no victim
    assert sched._pick_victim(ensuring=young) is None
    pool.free(old.blocks)
    pool.free(young.blocks)
    pool.free(young.blocks)


# ---------------------------------------------------------------------------
# engine-level contracts
# ---------------------------------------------------------------------------


def test_sharing_outputs_bit_identical_to_unshared():
    """Concurrent same-prefix streams with the prefix cache on emit
    exactly the tokens the unshared engine (and the contiguous-cache
    oracle) emits — the cached blocks hold bit-identical K/V and the
    prefill's logits don't depend on the write table."""
    prompt = list(range(1, 17))          # two full 8-token blocks
    tails = [[], [17], [18, 19], [20, 21, 22]]
    prompts = [prompt + t for t in tails]
    outs = {}
    for share in (False, True):
        cfg = _config(prefix_cache=share, prefills_per_step=1)
        eng = ServingEngine(cfg, seed=SEED)
        reqs = [eng.submit(p, 10) for p in prompts]
        while any(not r.finished() for r in reqs):
            eng.step()
        outs[share] = [list(r.generated) for r in reqs]
        if share:
            st = eng.pool.prefix_stats()
            assert st["hits"] >= 3 and st["hit_blocks"] >= 5, \
                "same-prefix admissions must hit the index: %s" % (st,)
        assert eng.pool.used() == 0
    assert outs[True] == outs[False]
    ex = _decode_executor(smodel.random_params(_config(), seed=SEED))
    for p, got in zip(prompts, outs[True]):
        assert got == _oracle_generate(ex, p, 10)


def test_sharing_multiplies_concurrent_streams_at_fixed_pool(monkeypatch):
    """The capacity headline: at the SAME pool size, shared-prefix
    streams that cannot all fit privately DO all fit with the prefix
    cache on (>= 2x the unshared peak here — above the 1.8x bar)."""
    # 2 blocks of 16 of prefix, tail in block 3. One decode step a
    # dispatch: a stream lives fifteen steps, so the fourth (a prefill a
    # step) is admitted while the first decodes
    from mxnet_tpu.serving import engine

    monkeypatch.setattr(engine, "DECODE_CHUNK", 1)
    prompt = [1 + i % 20 for i in range(32)]
    peaks = {}
    for share in (False, True):
        cfg = _config(prefix_cache=share, block_size=16, num_blocks=8,
                      max_batch=8, prefills_per_step=1)   # 7 usable blocks
        eng = ServingEngine(cfg, seed=SEED)
        reqs = [eng.submit(prompt, 16) for _ in range(4)]
        # streams decoding side by side (a stream may end inside the
        # decode chunk of the step that admits the fourth)
        batches, run_decode = [0], eng._run_decode
        eng._run_decode = lambda dec: (batches.append(len(dec)),
                                       run_decode(dec))[1]
        while any(not r.finished() for r in reqs):
            eng.step()
        peaks[share] = max(batches)
        assert all(r.state == "finished" for r in reqs)
        assert eng.pool.used() == 0
    # unshared: 3 blocks/stream -> 2 streams max in 7 blocks.
    # shared: 2 prefix blocks once + 1 private block each -> all 4 fit.
    assert peaks[False] <= 2
    assert peaks[True] >= 4
    assert peaks[True] >= 2 * peaks[False]


def test_preemption_invisible_with_sharing():
    """PR 10's preemption-invisibility acceptance with the prefix cache
    ON and shared blocks in the pool: evictions decrement refcounts,
    replays re-match the index, outputs stay equal to the oracle."""
    cfg = _config(prefix_cache=True, num_blocks=13, max_batch=4)
    eng = ServingEngine(cfg, seed=SEED)
    rng = np.random.RandomState(13)
    shared = [int(x) for x in rng.randint(0, cfg.vocab_size, 8)]
    prompts = [shared for _ in range(4)]   # one shared block each
    n_new = [20, 20, 20, 20]
    pre0 = telemetry.counter("serving.preemptions").value
    got = eng.generate(prompts, n_new)
    assert telemetry.counter("serving.preemptions").value > pre0, \
        "workload sized to force eviction saw none"
    ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
    want = _oracle_generate(ex, shared, 20)
    for g in got:
        assert g == want
    assert eng.pool.used() == 0
    assert eng.pool.prefix_stats()["index_size"] == 0


def test_engine_stats_and_metrics_expose_prefix_block():
    cfg = _config()
    eng = ServingEngine(cfg, seed=SEED)
    eng.generate([list(range(1, 17))], 4)
    s = eng.stats()
    assert s["prefix"]["enabled"] is True
    assert s["prefix"]["lookups"] >= 1
    assert "kv_bytes_saved" in s["prefix"]
    # the registry carries the counters (names pinned by METRIC_HELP +
    # the observability drift test)
    assert telemetry.counter("serving.prefix_lookups").value >= 1
