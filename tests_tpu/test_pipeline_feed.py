"""Input-pipeline feed tier (ci/run_tests.sh pipeline; docs/perf.md
§pipeline): the uint8-wire + on-device-normalize contract and the
double-buffered async device feed.

Host-only (tests_tpu/conftest.py exempts this file from the hardware
gate): everything here runs on the CPU backend — the wire/feed machinery
is identical on a real device, only the transfer cost differs.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import io as mio  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402

pytestmark = pytest.mark.pipeline

MEAN = (123.68, 116.28, 103.53)
STD = (58.395, 57.12, 57.375)


def _tiny_net():
    d = mx.sym.Variable("data")
    n = mx.sym.Convolution(d, num_filter=4, kernel=(3, 3), name="c1")
    n = mx.sym.Activation(n, act_type="relu")
    n = mx.sym.Flatten(n)
    n = mx.sym.FullyConnected(n, num_hidden=10, name="fc")
    return mx.sym.SoftmaxOutput(n, name="softmax")


def _uint8_dataset(n=64, hw=12):
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, size=(n, hw, hw, 3)).astype(np.uint8)
    labels = (np.arange(n) % 10).astype(np.float32)
    return imgs, labels


def _fit_params(it, epochs=2):
    mx.random.seed(7)
    mod = mx.mod.Module(_tiny_net())
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.init.Xavier(), force_init=True)
    arg, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in arg.items()}


# ---------------------------------------------------------------- uint8 wire
def test_uint8_wire_trains_identically_to_fp32_wire():
    """The acceptance bar: <1e-5 final-param delta, uint8 wire vs fp32 wire.

    Pixels are uint8-representable, so host fp32 normalize (fp32-wire path)
    and the deferred on-device normalize (uint8-wire path) compute the same
    fp32 values — training must be numerically indistinguishable."""
    imgs, labels = _uint8_dataset()
    wire = mio.WireSpec(mean=MEAN, std=STD)
    it_u8 = mx.io.NDArrayIter(imgs, labels, batch_size=8, wire=wire)
    imgs_f = ((imgs.astype(np.float32) - np.asarray(MEAN, np.float32))
              / np.asarray(STD, np.float32)).transpose(0, 3, 1, 2)
    it_f32 = mx.io.NDArrayIter(imgs_f, labels, batch_size=8)
    p_u8 = _fit_params(it_u8)
    p_f32 = _fit_params(it_f32)
    assert p_u8.keys() == p_f32.keys()
    for k in p_u8:
        assert np.abs(p_u8[k] - p_f32[k]).max() < 1e-5, k


def test_wire_ndarrayiter_advertises_decoded_desc():
    imgs, labels = _uint8_dataset(n=16, hw=8)
    it = mx.io.NDArrayIter(imgs, labels, batch_size=4,
                           wire=mio.WireSpec(mean=MEAN, std=STD))
    (desc,) = it.provide_data
    assert desc.shape == (4, 3, 8, 8)
    assert np.dtype(desc.dtype) == np.float32
    b = next(iter(it))
    assert b.data[0].dtype == np.uint8 and b.data[0].shape == (4, 8, 8, 3)
    dec = mio.apply_wire(b)
    assert dec.data[0].dtype == np.float32 and dec.data[0].shape == (4, 3, 8, 8)
    # idempotence: a decoded batch has no wire spec left
    assert getattr(dec, "wire", None) is None
    ref = ((b.data[0].asnumpy().astype(np.float32)
            - np.asarray(MEAN, np.float32)) / np.asarray(STD, np.float32)
           ).transpose(0, 3, 1, 2)
    np.testing.assert_allclose(dec.data[0].asnumpy(), ref, rtol=1e-6,
                               atol=1e-5)


def test_imagerecorditer_uint8_wire(tmp_path):
    pytest.importorskip("PIL")
    from rec_fixtures import gen_dataset, pack

    n, size = 16, 16
    img_dir, lst = gen_dataset(str(tmp_path), n, size)
    rec = pack(str(tmp_path), img_dir, lst)
    # both backends pinned to the Python pipeline: this test is the
    # python-path uint8-wire parity oracle (round 13 flipped the default
    # to the native stage; its own parity suite is test_native_decode)
    kw = dict(path_imgrec=rec, data_shape=(3, size, size), batch_size=4,
              preprocess_threads=1, backend="python",
              mean_r=MEAN[0], mean_g=MEAN[1], mean_b=MEAN[2],
              std_r=STD[0], std_g=STD[1], std_b=STD[2])
    it_f = mx.io_image.ImageRecordIter(wire_dtype="float32", **kw)
    ref = next(iter(it_f)).data[0].asnumpy()
    it_f.close()
    it_u = mx.io_image.ImageRecordIter(wire_dtype="uint8", **kw)
    b = next(iter(it_u))
    assert b.data[0].dtype == np.uint8 and b.data[0].shape == (4, size, size, 3)
    got = mio.apply_wire(b).data[0].asnumpy()
    it_u.close()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # the detection iterator refuses the wire mode loudly
    with pytest.raises(mx.base.MXNetError):
        mx.io_image.ImageDetRecordIter(
            path_imgrec=rec, data_shape=(3, size, size), batch_size=4,
            wire_dtype="uint8")


# ------------------------------------------------------------- device feed
class _CountingIter(mx.io.DataIter):
    """Hands out `total` tiny batches, counting how many were pulled."""

    def __init__(self, total=100, fail_at=None):
        super().__init__(batch_size=2)
        self.total = total
        self.fail_at = fail_at
        self.pulled = 0
        self.provide_data = [mx.io.DataDesc("data", (2, 3))]
        self.provide_label = [mx.io.DataDesc("softmax_label", (2,))]

    def reset(self):
        self.pulled = 0

    def next(self):
        if self.fail_at is not None and self.pulled == self.fail_at:
            raise ValueError("injected iterator fault")
        if self.pulled >= self.total:
            raise StopIteration
        self.pulled += 1
        return mx.io.DataBatch([mx.nd.ones((2, 3))], [mx.nd.zeros((2,))],
                               pad=0)


def test_feed_depth_respected():
    inner = _CountingIter(total=100)
    feed = mio.DeviceFeedIter(inner, ctx=mx.cpu(), depth=3)
    try:
        assert feed._q.maxsize == 3
        time.sleep(1.0)  # let the transfer thread run ahead as far as it can
        # bounded run-ahead: depth batches parked + at most one in flight
        assert inner.pulled <= 3 + 1, inner.pulled
        next(feed)
        time.sleep(0.5)
        assert inner.pulled <= 3 + 2, inner.pulled
    finally:
        feed.close()


def test_feed_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_FEED_DEPTH", "4")
    inner = _CountingIter(total=10)
    wrapped = mio.maybe_device_feed(inner, [mx.cpu()])
    try:
        assert isinstance(wrapped, mio.DeviceFeedIter)
        assert wrapped.depth == 4
        # idempotent: an existing feed is not re-wrapped
        assert mio.maybe_device_feed(wrapped, [mx.cpu()]) is wrapped
    finally:
        wrapped.close()
    monkeypatch.setenv("MXNET_FEED_DEPTH", "0")
    assert mio.maybe_device_feed(inner, [mx.cpu()]) is inner


def test_feed_streams_all_batches_and_resets():
    inner = _CountingIter(total=9)
    feed = mio.DeviceFeedIter(inner, ctx=mx.cpu(), depth=2)
    try:
        assert sum(1 for _ in feed) == 9
        feed.reset()
        assert sum(1 for _ in feed) == 9
        # terminal marker repeats instead of blocking
        with pytest.raises(StopIteration):
            feed.next()
    finally:
        feed.close()


def test_feed_teardown_never_strands_the_thread():
    # (a) close() mid-stream with a full queue
    inner = _CountingIter(total=1000)
    feed = mio.DeviceFeedIter(inner, ctx=mx.cpu(), depth=1)
    time.sleep(0.3)  # queue fills; transfer thread blocks in put
    t0 = time.time()
    feed.close()
    assert time.time() - t0 < 8, "close() stalled on a blocked producer"
    assert not feed._thread.is_alive(), "leaked transfer thread"
    with pytest.raises(StopIteration):
        feed.next()
    # (b) close() immediately after construction
    feed2 = mio.DeviceFeedIter(_CountingIter(total=5), ctx=mx.cpu(), depth=2)
    feed2.close()
    assert not feed2._thread.is_alive()
    # (c) no stray DeviceFeedIter threads left behind by (a)/(b)
    assert not [t for t in threading.enumerate()
                if t.name == "DeviceFeedIter" and t.is_alive()]


def test_feed_propagates_inner_exception():
    inner = _CountingIter(total=50, fail_at=2)
    feed = mio.DeviceFeedIter(inner, ctx=mx.cpu(), depth=2)
    try:
        with pytest.raises(ValueError, match="injected iterator fault"):
            for _ in feed:
                pass
        assert not feed._thread.is_alive()
        # post-fault next() terminates instead of blocking on a dead producer
        with pytest.raises(StopIteration):
            feed.next()
    finally:
        feed.close()


# ---------------------------------------------------------------- telemetry
def test_pipeline_stage_histograms_populate(tmp_path, monkeypatch):
    pytest.importorskip("PIL")
    from rec_fixtures import gen_dataset, pack

    n, size = 16, 16  # gen_dataset textures need size to be a multiple of 8
    img_dir, lst = gen_dataset(str(tmp_path), n, size)
    rec = pack(str(tmp_path), img_dir, lst)
    telemetry.reset()
    telemetry.enable()
    try:
        it = mx.io_image.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, size, size), batch_size=4,
            preprocess_threads=1, wire_dtype="uint8", backend="python")
        feed = mio.DeviceFeedIter(it, ctx=mx.cpu(), depth=2)
        assert sum(1 for _ in feed) == n // 4
        feed.close()
        it.close()
        snap = telemetry.dump(include_events=False)["histograms"]
        for stage in ("decode", "assemble", "upload", "feed_wait"):
            key = "pipeline.stage_seconds{stage=%s}" % stage
            assert snap.get(key, {}).get("count", 0) > 0, key
    finally:
        telemetry.disable()
        telemetry.reset()


def test_fit_uses_feed_via_env(monkeypatch):
    """MXNET_FEED_DEPTH makes fit's data wait a queue pop — and trains the
    same parameters as the direct path."""
    imgs, labels = _uint8_dataset(n=32, hw=8)
    wire = mio.WireSpec(mean=MEAN, std=STD)
    p_direct = _fit_params(mx.io.NDArrayIter(imgs, labels, batch_size=8,
                                             wire=wire))
    telemetry.reset()
    telemetry.enable()
    monkeypatch.setenv("MXNET_FEED_DEPTH", "2")
    inner = mx.io.NDArrayIter(imgs, labels, batch_size=8, wire=wire)
    try:
        p_feed = _fit_params(inner)
    finally:
        monkeypatch.delenv("MXNET_FEED_DEPTH")
        telemetry.disable()
    for k in p_direct:
        assert np.abs(p_direct[k] - p_feed[k]).max() < 1e-5, k
    snap = telemetry.dump(include_events=False)["histograms"]
    assert snap.get("io.batch_fetch_seconds{iter=DeviceFeedIter}",
                    {}).get("count", 0) > 0, "fit did not consume via the feed"
    telemetry.reset()
    # fit closed its owned feed and left the caller's iterator fresh
    assert not [t for t in threading.enumerate()
                if t.name == "DeviceFeedIter" and t.is_alive()]
    assert sum(1 for _ in inner) == 4
