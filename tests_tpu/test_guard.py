"""Training health guard suite: sentinel detection, skip/rollback/abort
policy ladder under injected faults, the stall watchdog, the iterator
position protocol, and exact mid-epoch resume determinism — all driven
through mxnet_tpu/fault.py so no real divergence, hang, or corrupt dataset
is needed.

Host-side only: runs on a CPU-only machine (tests_tpu/conftest.py exempts
this file from the hardware gate). `ci/run_tests.sh guard` is the CI tier.
"""
import hashlib
import os
import struct
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import fault, guard, telemetry  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.model import (  # noqa: E402
    load_latest_valid_checkpoint, load_resume_state, save_checkpoint)

pytestmark = pytest.mark.guard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RNG = np.random.RandomState(0)
_X = _RNG.randn(160, 4).astype(np.float32)
_Y = (_X.sum(axis=1) > 0).astype(np.float32)


def _make_iter(batch_size=16):
    return mx.io.NDArrayIter(_X, _Y, batch_size=batch_size)


def _net(num_hidden=2):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=num_hidden, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _make_module(num_hidden=2):
    return mx.mod.Module(_net(num_hidden), context=mx.cpu())


def _fit(mod, it, num_epoch=1, **kw):
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("optimizer_params", {"learning_rate": 0.1})
    mod.fit(it, num_epoch=num_epoch, **kw)


def _params_finite(mod):
    arg, aux = mod.get_params()
    return all(np.isfinite(v.asnumpy()).all()
               for v in list(arg.values()) + list(aux.values()))


def _hasher(log):
    """batch_end_callback recording (epoch, nbatch, sha1-of-batch-bytes)."""
    def cb(p):
        h = hashlib.sha1(
            p.locals["data_batch"].data[0].asnumpy().tobytes()).hexdigest()
        log.append((p.epoch, p.nbatch, h))
    return cb


# ---------------------------------------------------------------------------
# sentinel
# ---------------------------------------------------------------------------

def test_sentinel_flags_non_finite():
    s = guard.Sentinel()
    assert s.classify(float("nan"), 1.0) == "non_finite_loss"
    assert s.classify(1.0, float("inf")) == "non_finite_grad"
    assert s.classify(1.0, 1.0) is None


def test_sentinel_spike_needs_warmup_and_fires():
    s = guard.Sentinel(spike_factor=10.0, warmup_steps=5)
    for _ in range(4):
        assert s.classify(1.0, 1.0) is None
    # still inside warmup on the 5th good step: a spike passes
    assert s.classify(1.0, 1.0) is None
    assert s.classify(100.0, 1.0) == "loss_spike"
    assert s.classify(1.0, 100.0) == "grad_spike"
    # bad steps did NOT contaminate the EWMA: a normal step is still good
    assert s.classify(1.0, 1.0) is None


def test_sentinel_spike_disabled_by_default():
    s = guard.Sentinel()  # spike_factor 0
    for _ in range(50):
        s.classify(1.0, 1.0)
    assert s.classify(1e12, 1e12) is None  # huge but finite: not bad


def test_poison_grads_is_real(tmp_path):
    """The `nan` fault writes NaN into a REAL gradient array: applying the
    update corrupts the weights — what skip/rollback protect against."""
    mod = _make_module()
    it = _make_iter()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params()
    mod.init_optimizer()
    mod.forward_backward(it.next())
    assert guard._poison_grads(mod)
    mod.update()
    assert not _params_finite(mod)


# ---------------------------------------------------------------------------
# policy ladder through fit
# ---------------------------------------------------------------------------

def test_skip_policy_protects_params():
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip"))
    with fault.inject("nan:after=2,times=1"):
        _fit(mod, _make_iter(), guard=g)
    assert g.bad_steps == 1
    assert _params_finite(mod)
    assert telemetry.counter("guard.bad_steps",
                             reason="non_finite_grad").value >= 1


def test_nan_loss_target():
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip"))
    with fault.inject("nan:target=loss,times=1"):
        _fit(mod, _make_iter(), guard=g)
    assert g.bad_steps == 1
    assert _params_finite(mod)


def test_unguarded_fit_never_consults_nan_point():
    """Without a guard the sentinel (and its injection point) is never on
    the step path — the zero-overhead default."""
    mod = _make_module()
    with fault.inject("nan") as rules:
        _fit(mod, _make_iter())
        assert rules[0]["fired"] == 0
    assert _params_finite(mod)


def test_rollback_policy_heals_persistent_divergence():
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="rollback", max_bad_steps=2, max_rollbacks=3))
    seen = []
    with fault.inject("nan:after=3,times=4"):
        _fit(mod, _make_iter(), num_epoch=2, guard=g,
             batch_end_callback=_hasher(seen))
    assert g.rollbacks >= 1
    assert g.bad_steps == 4
    assert _params_finite(mod)
    # rollback rewound the iterator: some batch appears more than twice
    # (once per epoch is normal; the replayed span adds a third sighting)
    counts = {}
    for _, _, h in seen:
        counts[h] = counts.get(h, 0) + 1
    assert max(counts.values()) > 2


def test_rollback_replays_from_snapshot_batch():
    """After a rollback the NEXT trained batch is the snapshot's batch —
    exact-position recovery, not an approximate restart."""
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="rollback", max_bad_steps=1, max_rollbacks=1))
    seen = []
    # bad step at nbatch 3 -> immediate rollback to the epoch-start snapshot
    with fault.inject("nan:after=3,times=1"):
        _fit(mod, _make_iter(), guard=g, batch_end_callback=_hasher(seen))
    assert g.rollbacks == 1
    nbatches = [n for _, n, _ in seen]
    # batches 0..2 trained, the bad batch 3 never reaches callbacks (the
    # loop restarts first), then the epoch replays from the snapshot: 0..9
    assert nbatches[:4] == [0, 1, 2, 0]
    # and the replayed batch 0 is byte-identical to the first pass
    assert seen[3][2] == seen[0][2]


def test_abort_policy_raises_classified_error():
    mod = _make_module()
    with pytest.raises(guard.BadStepError, match="non_finite_grad"):
        with fault.inject("nan:times=1"):
            _fit(mod, _make_iter(), guard="abort")


def test_ladder_escalates_to_abort_after_max_rollbacks():
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="rollback", max_bad_steps=2, max_rollbacks=1))
    with pytest.raises(guard.BadStepError):
        with fault.inject("nan"):  # every step bad, forever
            _fit(mod, _make_iter(), guard=g)
    assert g.rollbacks == 1


def test_guard_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_GUARD_POLICY", "skip")
    mod = _make_module()
    with fault.inject("nan:times=1") as rules:
        _fit(mod, _make_iter())  # guard=None: resolved from the env
        assert rules[0]["fired"] == 1
    assert _params_finite(mod)


def test_resolve_rejects_bad_policy():
    with pytest.raises(MXNetError, match="MXNET_GUARD_POLICY"):
        guard.GuardPolicy(policy="explode")


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------

def test_stall_watchdog_raises_with_device_feed_active():
    feed = mx.io.DeviceFeedIter(_make_iter(), ctx=mx.cpu(), depth=1)
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                              stall_timeout_s=1.0))
    stalls_before = telemetry.counter("guard.stalls").value
    t0 = time.time()
    try:
        with pytest.raises(guard.StallError, match="MXNET_GUARD_STALL_S"):
            with fault.inject("stall:after=2,delay_ms=30000,times=1"):
                _fit(mod, feed, num_epoch=3, guard=g)
        assert time.time() - t0 < 20  # did not sit out the 30s sleep
        assert telemetry.counter("guard.stalls").value == stalls_before + 1
    finally:
        feed.close()


def test_watchdog_does_not_false_fire():
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                              stall_timeout_s=30.0))
    _fit(mod, _make_iter(), guard=g)
    assert not g.stall_fired


# ---------------------------------------------------------------------------
# iterator position protocol
# ---------------------------------------------------------------------------

def _drain_hashes(it, n=None):
    out = []
    while True:
        try:
            b = it.next()
        except StopIteration:
            return out
        out.append(hashlib.sha1(b.data[0].asnumpy().tobytes()).hexdigest())
        if n is not None and len(out) >= n:
            return out


def test_ndarray_iter_state_roundtrip():
    it = _make_iter()
    for _ in range(3):
        it.next()
    state = it.state_dict()
    rest = _drain_hashes(it)
    it2 = _make_iter()
    it2.load_state(state)
    assert _drain_hashes(it2) == rest


def test_resize_iter_state_roundtrip():
    it = mx.io.ResizeIter(_make_iter(), 7)
    for _ in range(3):
        it.next()
    state = it.state_dict()
    rest = _drain_hashes(it)
    it2 = mx.io.ResizeIter(_make_iter(), 7)
    it2.load_state(state)
    assert _drain_hashes(it2) == rest


def test_prefetching_iter_state_reflects_delivered_batches():
    it = mx.io.PrefetchingIter(_make_iter())
    for _ in range(3):
        it.next()
    state = it.state_dict()
    # the producer prefetched batch 3 already; the state must describe the
    # 3 DELIVERED batches (cursor sits on batch 2, resume yields batch 3)
    assert state["inner"][0]["cursor"] == 2 * 16
    rest = _drain_hashes(it)
    it2 = mx.io.PrefetchingIter(_make_iter())
    it2.load_state(state)
    assert _drain_hashes(it2) == rest


def test_device_feed_iter_state_passthrough():
    feed = mx.io.DeviceFeedIter(_make_iter(), ctx=mx.cpu(), depth=2)
    try:
        for _ in range(3):
            feed.next()
        state = feed.state_dict()
        # 3 delivered (cursor on batch 2) — in-flight queue depth not counted
        assert state["inner"]["cursor"] == 2 * 16
        rest = _drain_hashes(feed)
    finally:
        feed.close()
    feed2 = mx.io.DeviceFeedIter(_make_iter(), ctx=mx.cpu(), depth=2)
    try:
        feed2.load_state(state)
        assert _drain_hashes(feed2) == rest
    finally:
        feed2.close()


def test_base_iter_state_unsupported():
    it = mx.io.DataIter()
    assert it.state_dict() is None
    with pytest.raises(MXNetError):
        it.load_state({})


@pytest.fixture(scope="module")
def small_rec(tmp_path_factory):
    from rec_fixtures import gen_dataset, pack

    workdir = str(tmp_path_factory.mktemp("rec"))
    img_dir, lst = gen_dataset(workdir, n=24, size=32)
    return pack(workdir, img_dir, lst)


def test_image_record_iter_state_fast_forward(small_rec):
    kw = dict(path_imgrec=small_rec, data_shape=(3, 32, 32), batch_size=4,
              preprocess_threads=1, seed=7)
    it = mx.io_image.ImageRecordIter(**kw)
    try:
        for _ in range(2):
            it.next()
        state = it.state_dict()
        assert state == {"type": "ImageRecordIter", "epoch": 0, "batches": 2}
        rest = _drain_hashes(it, n=2)
    finally:
        it.close()
    it2 = mx.io_image.ImageRecordIter(**kw)
    try:
        it2.load_state(state)
        assert _drain_hashes(it2, n=2) == rest
    finally:
        it2.close()


# ---------------------------------------------------------------------------
# bad-record quarantine
# ---------------------------------------------------------------------------

def test_image_record_iter_skips_bad_records_by_default(small_rec):
    before = telemetry.counter("io.bad_records", source="decode").value
    # backend pinned: fault.inject('bad_record') hooks the PYTHON decode
    # workers (the native stage's quarantine has its own suite in
    # test_native_decode.py, driven by genuinely corrupt records)
    it = mx.io_image.ImageRecordIter(
        path_imgrec=small_rec, data_shape=(3, 32, 32), batch_size=4,
        preprocess_threads=1, backend="python")
    try:
        with fault.inject("bad_record:times=2"):
            n = len(_drain_hashes(it))
    finally:
        it.close()
    # 24 records, 2 quarantined -> 22 images -> 5 full batches + padded tail
    assert n == 6
    assert telemetry.counter("io.bad_records",
                             source="decode").value == before + 2


def test_image_record_iter_fails_fast_past_budget(small_rec, monkeypatch):
    monkeypatch.setenv("MXNET_IO_MAX_BAD_RECORDS", "1")
    it = mx.io_image.ImageRecordIter(
        path_imgrec=small_rec, data_shape=(3, 32, 32), batch_size=4,
        preprocess_threads=1, backend="python")
    try:
        with fault.inject("bad_record"):  # every record bad
            with pytest.raises(MXNetError, match="MXNET_IO_MAX_BAD_RECORDS"):
                _drain_hashes(it)
    finally:
        it.close()


def _write_rec(path, payloads):
    w = mx.recordio.MXRecordIO(path, "w")
    offs = []
    for p in payloads:
        offs.append(w.tell())
        w.write(p)
    w.close()
    return offs


def test_recordio_strict_raises_on_corrupt_stream(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_IO_MAX_BAD_RECORDS", raising=False)
    path = str(tmp_path / "a.rec")
    offs = _write_rec(path, [b"one!", b"two!", b"three!!"])
    raw = bytearray(open(path, "rb").read())
    struct.pack_into("<I", raw, offs[1], 0xDEADBEEF)  # trash record 2's magic
    open(path, "wb").write(bytes(raw))
    r = mx.recordio.MXRecordIO(path, "r")
    assert r.read() == b"one!"
    with pytest.raises(MXNetError, match="bad record"):
        r.read()
    r.close()


def test_recordio_resyncs_within_budget(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_IO_MAX_BAD_RECORDS", "5")
    before = telemetry.counter("io.bad_records", source="stream").value
    path = str(tmp_path / "a.rec")
    offs = _write_rec(path, [b"one!", b"two!", b"three!!"])
    raw = bytearray(open(path, "rb").read())
    struct.pack_into("<I", raw, offs[1], 0xDEADBEEF)
    open(path, "wb").write(bytes(raw))
    r = mx.recordio.MXRecordIO(path, "r")
    got = []
    while True:
        s = r.read()
        if s is None:
            break
        got.append(s)
    r.close()
    assert got == [b"one!", b"three!!"]  # record two quarantined, not fatal
    assert telemetry.counter("io.bad_records",
                             source="stream").value > before


def test_recordio_truncated_tail_raises_strict(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_IO_MAX_BAD_RECORDS", raising=False)
    path = str(tmp_path / "a.rec")
    _write_rec(path, [b"0123456789abcdef"])
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:12])  # header promises 16 bytes; 4 present
    r = mx.recordio.MXRecordIO(path, "r")
    with pytest.raises(MXNetError, match="truncated"):
        r.read()
    r.close()


# ---------------------------------------------------------------------------
# exact mid-epoch resume
# ---------------------------------------------------------------------------

def test_exact_mid_epoch_resume_determinism(tmp_path):
    """The acceptance scenario: guard checkpoints mid-epoch; the job dies
    mid-epoch; auto_resume lands on the exact next batch and the
    post-recovery batch sequence is byte-identical to an uninterrupted
    run's — nothing replayed, nothing skipped."""
    prefix = str(tmp_path / "job")

    def _seed():
        # identical parameter initialization across runs A and B, so B's
        # checkpoint params equal A's at the same step and the resumed
        # model can be compared to A elementwise
        mx.random.seed(42)
        np.random.seed(42)

    run_a = []
    mod_a = _make_module()
    _seed()
    _fit(mod_a, _make_iter(), num_epoch=2, batch_end_callback=_hasher(run_a))

    run_b = []

    def crasher(p):
        if p.epoch == 1 and p.nbatch == 7:
            raise fault.InjectedCrash("mid-epoch death")

    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="skip", checkpoint_prefix=prefix, checkpoint_every=3))
    _seed()
    with pytest.raises(fault.InjectedCrash):
        _fit(_make_module(), _make_iter(), num_epoch=2, guard=g,
             batch_end_callback=[_hasher(run_b), crasher])
    # a mid-epoch checkpoint with a .resume sidecar exists for epoch 1
    assert os.path.exists("%s-0001.params" % prefix)
    state = load_resume_state(prefix, 1)
    assert state is not None and state["nbatch"] > 0

    run_c = []
    mod_c = _make_module()
    _fit(mod_c, _make_iter(), num_epoch=2, batch_end_callback=_hasher(run_c),
         auto_resume=prefix)
    # resumed mid-epoch 1, at the batch right after the last checkpoint
    assert run_c[0][0] == 1 and run_c[0][1] == state["nbatch"]
    # byte-identical continuation of the uninterrupted run
    assert run_c == run_a[run_a.index(run_c[0]):]
    # and the final model matches the uninterrupted one exactly
    arg_a, _ = mod_a.get_params()
    arg_c, _ = mod_c.get_params()
    for k in arg_a:
        np.testing.assert_array_equal(arg_a[k].asnumpy(), arg_c[k].asnumpy())


def test_old_checkpoint_resumes_at_epoch_boundary(tmp_path):
    """Pre-guard checkpoints (no sidecar) keep the PR-1 behavior: resume at
    the epoch boundary."""
    prefix = str(tmp_path / "job")
    _fit(_make_module(), _make_iter(), num_epoch=2,
         epoch_end_callback=mx.callback.do_checkpoint(prefix))
    assert load_resume_state(prefix, 2) is None
    seen = []
    _fit(_make_module(), _make_iter(), num_epoch=3,
         batch_end_callback=_hasher(seen), auto_resume=prefix)
    assert seen[0][:2] == (2, 0)  # epoch 2 from its first batch


def test_boundary_save_retires_stale_sidecar(tmp_path):
    """An epoch-boundary save over a guard mid-epoch checkpoint of the same
    epoch number must clear the sidecar — otherwise resume would skip
    batches these params never trained on."""
    prefix = str(tmp_path / "job")
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="skip", checkpoint_prefix=prefix, checkpoint_every=3))
    _fit(mod, _make_iter(), num_epoch=1, guard=g)
    assert load_resume_state(prefix, 0) is not None  # mid-epoch-0 sidecar
    save_checkpoint(prefix, 0, mod.symbol, *mod.get_params())
    assert load_resume_state(prefix, 0) is None


def test_sidecar_bound_to_params_by_crc(tmp_path):
    """A sidecar whose params file was replaced (torn mid-epoch checkpoint,
    manual copy) is ignored — degrade to epoch-boundary resume."""
    prefix = str(tmp_path / "job")
    mod = _make_module()
    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="skip", checkpoint_prefix=prefix, checkpoint_every=3))
    _fit(mod, _make_iter(), num_epoch=1, guard=g)
    assert load_resume_state(prefix, 0) is not None
    mx.nd.save("%s-0000.params" % prefix,
               {"arg:fc_weight": mx.nd.ones((2, 4)),
                "arg:fc_bias": mx.nd.zeros((2,))})
    assert load_resume_state(prefix, 0) is None


# ---------------------------------------------------------------------------
# optimizer-state shape mismatch -> warm start
# ---------------------------------------------------------------------------

def _checkpoint_with_states(prefix, num_hidden):
    mod = _make_module(num_hidden)
    _fit(mod, _make_iter(), num_epoch=1,
         optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
         epoch_end_callback=mx.callback.module_checkpoint(
             mod, prefix, save_optimizer_states=True))
    return mod


def test_stale_states_shape_mismatch_warm_starts(tmp_path):
    """The model was edited between runs: the params file matches the new
    model but a stale .states (old shapes) sits beside it. fit must log
    and warm-start instead of dying inside the first optimizer update."""
    prefix = str(tmp_path / "job")
    _checkpoint_with_states(prefix, num_hidden=8)  # old model's .states
    states = open("%s-0001.states" % prefix, "rb").read()
    # new (edited) model writes its params over the checkpoint, but the
    # stale .states survives (do_checkpoint never writes/clears .states)
    new_mod = _make_module(num_hidden=2)
    _fit(new_mod, _make_iter(), num_epoch=1,
         epoch_end_callback=mx.callback.do_checkpoint(prefix))
    open("%s-0001.states" % prefix, "wb").write(states)
    # resume with the new model: loads params, rejects the stale states,
    # keeps training (regression: this died inside optimizer.update)
    mod = _make_module(num_hidden=2)
    _fit(mod, _make_iter(), num_epoch=2,
         optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
         auto_resume=prefix)
    assert _params_finite(mod)


def test_load_optimizer_states_raises_clear_error(tmp_path):
    prefix = str(tmp_path / "job")
    _checkpoint_with_states(prefix, num_hidden=8)
    mod = _make_module(num_hidden=2)
    it = _make_iter()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    with pytest.raises(MXNetError, match="do not match this model"):
        mod.load_optimizer_states("%s-0001.states" % prefix)
    # the updater was left clean: training proceeds as a warm start
    _fit(mod, it, num_epoch=1,
         optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert _params_finite(mod)


# ---------------------------------------------------------------------------
# review regressions
# ---------------------------------------------------------------------------

def test_bad_steps_heartbeat_the_watchdog():
    """A completing-but-bad step is progress, not a stall: a NaN streak
    under the skip policy must keep the watchdog fed."""
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                              stall_timeout_s=60.0))
    g.start()
    try:
        wd = g._watchdog
        assert wd._last is None  # unarmed before any step
        wd.suspend()
        assert wd._last is None  # suspending an unarmed watchdog: still off
        g.bad_step("non_finite_grad", 0, 0)
        assert wd._last is not None  # bad step beat it
        beat_at = wd._last
        wd.suspend()
        # bounded blind spot, not disarmed: the deadline is pushed out by
        # GRACE x timeout, so a genuine hang inside boundary work still fires
        assert wd._last is not None and wd._last > beat_at
        assert not wd.fired
    finally:
        g.close()


def test_fired_watchdog_replaced_on_guard_reuse():
    """A guard reused after a stall gets a FRESH watchdog: fit #2 keeps
    stall protection, and its stall_fired flag starts clean (a real Ctrl-C
    must not be misread as the old stall)."""
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                              stall_timeout_s=60.0))
    g.start()
    first = g._watchdog
    first.fired = True  # simulate a fired stall
    g._stall_raised = True
    g.close()
    assert g.stall_fired  # sticky until the next fit starts
    g.start()
    assert g._watchdog is not first
    assert not g.stall_fired and not g._stall_raised
    g.close()


def test_indexed_recordio_stays_strict_despite_budget(tmp_path, monkeypatch):
    """Random access must never resync: returning the next physical record
    under the requested index would silently alias data."""
    monkeypatch.setenv("MXNET_IO_MAX_BAD_RECORDS", "5")
    rec_path = str(tmp_path / "a.rec")
    idx_path = str(tmp_path / "a.idx")
    w = mx.recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    offs = []
    for i in range(3):
        offs.append(w.tell())
        w.write_idx(i, b"payload-%d!!" % i)
    w.close()
    raw = bytearray(open(rec_path, "rb").read())
    struct.pack_into("<I", raw, offs[1], 0xDEADBEEF)
    open(rec_path, "wb").write(bytes(raw))
    r = mx.recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    assert r.read_idx(0) == b"payload-0!!"
    with pytest.raises(MXNetError):
        r.read_idx(1)
    r.close()


def test_sidecar_ignored_when_begin_epoch_raised(tmp_path):
    """A caller-raised begin_epoch above the sidecar's epoch must not
    fast-forward the later epoch by the sidecar's batch count."""
    prefix = str(tmp_path / "job")
    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="skip", checkpoint_prefix=prefix, checkpoint_every=3))
    _fit(_make_module(), _make_iter(), num_epoch=1, guard=g)
    assert load_resume_state(prefix, 0) is not None
    seen = []
    _fit(_make_module(), _make_iter(), num_epoch=3, begin_epoch=2,
         batch_end_callback=_hasher(seen), auto_resume=prefix)
    assert seen[0][:2] == (2, 0)  # epoch 2 from batch 0, nothing skipped


def test_watchdog_survives_slow_epoch_boundary_work():
    """Validation/checkpoint callbacks at the epoch boundary can exceed the
    stall deadline; fit suspends the watchdog there, so a slow epoch end is
    not a stall."""
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                              stall_timeout_s=0.6))

    def slow_epoch_end(*_a):
        time.sleep(1.5)  # well past the deadline

    mod = _make_module()
    _fit(mod, _make_iter(), num_epoch=2, guard=g,
         epoch_end_callback=slow_epoch_end)
    assert not g.stall_fired


def test_fused_style_applied_bad_steps_escalate_under_skip():
    """skip cannot protect a bad update that already reached the params
    (fused-path post-step detection): after max_bad_steps consecutive
    applied-bad steps the ladder aborts instead of burning the budget."""
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                              max_bad_steps=3))
    assert g.bad_step("non_finite_loss", 0, 0, applied=True) == "skip"
    assert g.bad_step("non_finite_loss", 0, 1, applied=True) == "skip"
    assert g.bad_step("non_finite_loss", 0, 2, applied=True) == "abort"
    # pre-update (classic-path) detections under skip never escalate
    g2 = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                               max_bad_steps=3))
    for n in range(10):
        assert g2.bad_step("non_finite_grad", 0, n) == "skip"


def test_resolve_does_not_mutate_callers_policy(tmp_path):
    """A GuardPolicy reused across fits keeps following each fit's
    auto_resume prefix instead of being pinned to the first one."""
    pol = guard.GuardPolicy(policy="skip", checkpoint_every=3)
    g_a = guard.resolve(pol, checkpoint_prefix=str(tmp_path / "run_a"))
    g_b = guard.resolve(pol, checkpoint_prefix=str(tmp_path / "run_b"))
    assert pol.checkpoint_prefix is None  # caller's object untouched
    assert g_a.checkpoint_prefix.endswith("run_a")
    assert g_b.checkpoint_prefix.endswith("run_b")
    # a reused TrainingGuard re-targets per fit the same way
    g = guard.TrainingGuard(guard.GuardPolicy(policy="skip",
                                              checkpoint_every=3))
    guard.resolve(g, checkpoint_prefix=str(tmp_path / "x"))
    assert g.checkpoint_prefix.endswith("x")
    guard.resolve(g, checkpoint_prefix=str(tmp_path / "y"))
    assert g.checkpoint_prefix.endswith("y")
    # an explicit policy prefix always wins over the fit default
    gp = guard.TrainingGuard(guard.GuardPolicy(
        policy="skip", checkpoint_prefix=str(tmp_path / "pinned")))
    guard.resolve(gp, checkpoint_prefix=str(tmp_path / "z"))
    assert gp.checkpoint_prefix.endswith("pinned")


def test_env_int_garbage_degrades_to_default(monkeypatch):
    from mxnet_tpu.base import env_int

    monkeypatch.setenv("MXNET_IO_MAX_BAD_RECORDS", "five")
    assert env_int("MXNET_IO_MAX_BAD_RECORDS", None) is None
    monkeypatch.setenv("MXNET_IO_MAX_BAD_RECORDS", " 7 ")
    assert env_int("MXNET_IO_MAX_BAD_RECORDS", None) == 7


# ---------------------------------------------------------------------------
# rollback + resume compose (slow: several fits)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_rollback_then_resume_end_to_end(tmp_path):
    """Multi-rollback run followed by a crash and an exact resume: the two
    recovery layers (in-memory rollback, on-disk resume) compose."""
    prefix = str(tmp_path / "job")
    g = guard.TrainingGuard(guard.GuardPolicy(
        policy="rollback", max_bad_steps=2, max_rollbacks=3,
        checkpoint_prefix=prefix, checkpoint_every=4))

    def crasher(p):
        if p.epoch == 1 and p.nbatch == 6:
            raise fault.InjectedCrash("die")

    with pytest.raises(fault.InjectedCrash):
        with fault.inject("nan:after=2,times=4"):
            _fit(_make_module(), _make_iter(), num_epoch=2, guard=g,
                 batch_end_callback=crasher)
    assert g.rollbacks >= 1
    ckpt = load_latest_valid_checkpoint(prefix)
    assert ckpt is not None
    seen = []
    mod = _make_module()
    _fit(mod, _make_iter(), num_epoch=2, batch_end_callback=_hasher(seen),
         auto_resume=prefix)
    state = load_resume_state(prefix, ckpt[3])
    if state is not None:
        assert seen[0][:2] == (ckpt[3], state["nbatch"])
    assert _params_finite(mod)
