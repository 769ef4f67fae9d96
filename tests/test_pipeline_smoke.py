"""End-to-end rec-file training smoke (VERDICT round-3 item 5's CI piece):
synthetic JPEGs -> tools/im2rec.py pack -> ImageRecordIter decode/augment/
batch -> Module.fit: this test pins the correctness of the full path.
"""
import os

import numpy as np
import pytest

pytest.importorskip("PIL")


def test_jpeg_to_rec_to_fit(tmp_path):
    import mxnet_tpu as mx
    from rec_fixtures import gen_dataset, pack

    n, size, batch = 64, 32, 16
    img_dir, lst = gen_dataset(str(tmp_path), n, size)
    rec = pack(str(tmp_path), img_dir, lst)
    assert os.path.exists(rec) and os.path.exists(rec[:-4] + ".idx")

    it = mx.io_image.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, size, size), batch_size=batch,
        preprocess_threads=2, shuffle=True)
    # one full pass: batches have the declared shape and live pixel range
    seen = 0
    for b in it:
        arr = b.data[0].asnumpy()
        assert arr.shape == (batch, 3, size, size)
        assert arr.max() > 1.0  # raw 0..255 pixels (no silent normalize)
        seen += batch - b.pad
    assert seen == n
    it.reset()

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                             stride=(2, 2), name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net)
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier(), eval_metric="acc",
            force_init=True)
    # the labels cycle i%10 over random textures — no learnable signal;
    # the assertion is that the full pipeline trains without error and
    # produces finite params
    arg, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in arg.values())


def test_close_then_next_raises_and_custom_aug_fallback(tmp_path):
    """Round-4 pipeline hardening: (a) close() is terminal — next() raises
    StopIteration instead of blocking; (b) a custom augmenter that only
    implements __call__ (no apply_np override) routes the workers onto the
    NDArray chain and still produces correct batches."""
    import mxnet_tpu as mx
    from mxnet_tpu.image import Augmenter
    from rec_fixtures import gen_dataset, pack

    n, size = 16, 24
    img_dir, lst = gen_dataset(str(tmp_path), n, size)
    rec = pack(str(tmp_path), img_dir, lst)

    # (a) close -> StopIteration
    it = mx.io_image.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, size, size), batch_size=4,
        preprocess_threads=2)
    next(iter(it))
    it.close()
    with pytest.raises(StopIteration):
        it.next()

    # (b) __call__-only augmenter disables the numpy fast path but works
    class Invert(Augmenter):          # overrides __call__ only
        def __call__(self, src):
            import mxnet_tpu as mx
            return mx.nd.array(255.0 - src.asnumpy())

    it2 = mx.io_image.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, size, size), batch_size=4,
        preprocess_threads=1)
    plain = next(iter(it2)).data[0].asnumpy()
    it2.close()

    it3 = mx.io_image.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, size, size), batch_size=4,
        preprocess_threads=1)
    it3.auglist.append(Invert())
    it3.reset()                        # restart workers with the new auglist
    inverted = next(iter(it3)).data[0].asnumpy()
    it3.close()
    np.testing.assert_allclose(inverted, 255.0 - plain, atol=1e-4)


def test_close_with_full_prefetch_queue(tmp_path):
    """close() while the batcher is blocked on a full prefetch queue: the
    close-is-terminal contract must hold (no stale batch before the marker)
    and all pipeline threads must actually exit."""
    import time
    import mxnet_tpu as mx
    from rec_fixtures import gen_dataset, pack

    n, size = 32, 16
    img_dir, lst = gen_dataset(str(tmp_path), n, size)
    rec = pack(str(tmp_path), img_dir, lst)

    # pinned to the Python pipeline: the contract under test is ITS thread
    # teardown (the native stage has no Python pipeline threads to leak)
    it = mx.io_image.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, size, size), batch_size=4,
        preprocess_threads=2, prefetch_buffer=1, backend="python")
    time.sleep(0.5)               # let the pipeline fill the 1-slot queue
    t0 = time.time()
    it.close()
    assert time.time() - t0 < 8, "close() stalled on a blocked producer"
    with pytest.raises(StopIteration):
        it.next()
    assert not any(t.is_alive() for t in it._threads), "leaked threads"
