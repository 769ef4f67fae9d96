"""Evaluation metrics (reference: python/mxnet/metric.py — EvalMetric :27,
create :148, CompositeEvalMetric :192, Accuracy :322, TopKAccuracy :387, F1 :461,
Perplexity :556, MAE/MSE/RMSE :661-778, CrossEntropy :837, Loss :901,
CustomMetric :945, np() wrapper :1025)."""
from __future__ import annotations

import math

import numpy

from .base import numeric_types, string_types

__all__ = [
    "EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy", "F1",
    "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy", "Loss", "Torch", "Caffe",
    "CustomMetric", "MApMetric", "np", "create",
]


def _as_numpy(arr):
    """NDArray-aware host conversion (numpy.asarray on an NDArray recurses
    through lazy __getitem__ views instead of fetching)."""
    from . import ndarray as nd

    return arr.asnumpy() if isinstance(arr, nd.NDArray) else numpy.asarray(arr)


def check_label_shapes(labels, preds, shape=0):
    if shape == 0:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError(
            "Shape of labels {} does not match shape of predictions {}".format(label_shape, pred_shape)
        )


class EvalMetric:
    """Base class for all evaluation metrics (reference: metric.py:27)."""

    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self.reset()

    def update(self, labels, preds):
        raise NotImplementedError()

    def reset(self):
        if self.num is None:
            self.num_inst = 0
            self.sum_metric = 0.0
        else:
            self.num_inst = [0] * self.num
            self.sum_metric = [0.0] * self.num

    def get(self):
        if self.num is None:
            if self.num_inst == 0:
                return (self.name, float("nan"))
            return (self.name, self.sum_metric / self.num_inst)
        names = ["%s_%d" % (self.name, i) for i in range(self.num)]
        values = [
            x / y if y != 0 else float("nan") for x, y in zip(self.sum_metric, self.num_inst)
        ]
        return (names, values)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))


class CompositeEvalMetric(EvalMetric):
    """Manage multiple metrics (reference: metric.py:192)."""

    def __init__(self, metrics=None, **kwargs):
        super().__init__("composite", **kwargs)
        if metrics is None:
            metrics = []
        self.metrics = [create(m) if isinstance(m, str) else m for m in metrics]

    def add(self, metric):
        self.metrics.append(create(metric) if isinstance(metric, str) else metric)

    def get_metric(self, index):
        try:
            return self.metrics[index]
        except IndexError:
            return ValueError("Metric index {} is out of range 0 and {}".format(index, len(self.metrics)))

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def reset(self):
        try:
            for metric in self.metrics:
                metric.reset()
        except AttributeError:
            pass

    def get(self):
        names = []
        results = []
        for metric in self.metrics:
            result = metric.get()
            name = result[0]
            if isinstance(name, string_types):
                name = [name]
                result = [result[1]]
            else:
                result = result[1]
            names.extend(name)
            results.extend(result)
        return (names, results)


class _DeferredCountMetric(EvalMetric):
    """Base for metrics whose per-batch statistic is an integer count over
    device arrays (correct predictions, top-k hits).

    TPU-native accumulation: the count is computed by ONE jitted program per
    batch and added into a device-resident scalar — no host fetch in the hot
    loop. ``get()`` folds the accumulator into ``sum_metric`` with a single
    blocking fetch (per epoch in the fit loop). The per-batch fetch the
    reference does would stall the dispatch queue every step; this defers it
    entirely, which is why Module.fit's throughput survives metric updates.
    Host/numpy preds fall back to the reference's eager path.
    """

    def __init__(self, name, num=None):
        super().__init__(name, num=num)
        self._dev_count = {}  # device-set -> device-resident running count
        self._count_fns = {}

    def reset(self):
        super().reset()
        self._dev_count = {}

    def _flush(self):
        for acc in self._dev_count.values():
            self.sum_metric += int(numpy.asarray(acc))
        self._dev_count = {}

    def get(self):
        self._flush()
        return super().get()

    def _accumulate(self, key, build_fn, *arrays):
        """Run (and cache) the jitted count program, chaining a per-device-set
        accumulator through a donated argument (executor groups emit outputs
        committed to different devices; each keeps its own running count)."""
        import jax
        import numpy as np

        fn = self._count_fns.get(key)
        if fn is None:
            from . import compileobs

            fn = compileobs.jit(
                build_fn, "metric.count",
                site="mxnet_tpu/metric.py:_DeferredCountMetric._accumulate",
                graph_key=(type(self).__name__, key), donate_argnums=(0,))
            self._count_fns[key] = fn
        ref = arrays[0]
        ref_devs = ref.devices()
        fixed = [ref]
        for a in arrays[1:]:
            if hasattr(a, "devices") and a.devices() != ref_devs:
                if all(d.platform == "cpu" for d in a.devices()):
                    # host-side label: a local copy, no accelerator round-trip;
                    # jit re-places it beside the predictions (async upload)
                    a = numpy.asarray(a)
                elif len(ref_devs) == 1:
                    a = jax.device_put(a, next(iter(ref_devs)))
                else:
                    # sharded predictions: replicate the label over the same
                    # mesh (async) rather than a blocking host fetch
                    try:
                        from jax.sharding import (
                            NamedSharding, PartitionSpec as _P,
                        )

                        a = jax.device_put(
                            a, NamedSharding(ref.sharding.mesh, _P())
                        )
                    except (AttributeError, TypeError, ValueError):
                        a = numpy.asarray(a)
            fixed.append(a)
        devkey = tuple(sorted(d.id for d in ref_devs))
        acc = self._dev_count.get(devkey)
        if acc is None:
            # place the initial zero beside the predictions so the donation
            # is honored from the first call (a host scalar would emit a
            # 'donated buffers were not usable' warning into user logs)
            zero = np.int32(0)
            if len(ref_devs) == 1:
                acc = jax.device_put(zero, next(iter(ref_devs)))
            else:
                try:
                    from jax.sharding import NamedSharding, PartitionSpec as _P

                    acc = jax.device_put(
                        zero, NamedSharding(ref.sharding.mesh, _P()))
                except (AttributeError, TypeError, ValueError):
                    acc = zero
        self._dev_count[devkey] = fn(acc, *fixed)


class Accuracy(_DeferredCountMetric):
    """Classification accuracy (reference: metric.py:322), accumulated on
    device (see _DeferredCountMetric)."""

    def __init__(self, axis=1, name="accuracy"):
        super().__init__(name)
        self.axis = axis

    def update(self, labels, preds):
        from . import ndarray as nd

        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            if not isinstance(pred_label, nd.NDArray):
                self._update_host(label, pred_label)
                continue
            # keep labels wherever they live: fetching them per batch would
            # reintroduce the blocking round-trip this class exists to avoid
            label_arr = label.data if isinstance(label, nd.NDArray) else numpy.asarray(label)
            axis = self.axis
            shape = pred_label.shape
            # reference rule (metric.py:334): predictions are argmaxed over
            # `axis` exactly when their shape differs from the labels'
            need_argmax = len(shape) > 1 and tuple(shape) != tuple(label_arr.shape)
            n_pred = int(numpy.prod(shape))
            if need_argmax:
                n_pred //= shape[axis]  # the dim argmax removes
            n_lab = int(numpy.prod(label_arr.shape))
            if n_lab != n_pred:
                raise ValueError(
                    "Shape of labels %d does not match shape of predictions %d"
                    % (n_lab, n_pred)
                )

            def count(acc, p, l, _argmax=need_argmax, _axis=axis):
                import jax.numpy as jnp

                ids = jnp.argmax(p, axis=_axis) if _argmax else p
                return acc + jnp.sum(
                    jnp.ravel(ids).astype(jnp.int32)
                    == jnp.ravel(l).astype(jnp.int32)
                ).astype(jnp.int32)

            self._accumulate(
                ("acc", need_argmax, shape, tuple(label_arr.shape)),
                count, pred_label.data, label_arr,
            )
            self.num_inst += int(numpy.prod(label_arr.shape))

    def _update_host(self, label, pred_label):
        pred_np = numpy.asarray(pred_label)
        label_shape = numpy.shape(_as_numpy(label))
        if pred_np.ndim > 1 and pred_np.shape != label_shape:
            pred_np = numpy.argmax(pred_np, axis=self.axis)
        pred_np = pred_np.astype("int32").reshape(-1)
        label_np = _as_numpy(label).astype("int32").reshape(-1)
        check_label_shapes(label_np, pred_np)
        self.sum_metric += (pred_np == label_np).sum()
        self.num_inst += len(pred_np)


class TopKAccuracy(_DeferredCountMetric):
    """Top-k accuracy (reference: metric.py:387), accumulated on device via
    lax.top_k (see _DeferredCountMetric)."""

    def __init__(self, top_k=1, name="top_k_accuracy"):
        super().__init__(name)
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += "_%d" % self.top_k

    def update(self, labels, preds):
        from . import ndarray as nd

        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            assert len(pred_label.shape) <= 2, "Predictions should be no more than 2 dims"
            if not isinstance(pred_label, nd.NDArray):
                self._update_host(label, pred_label)
                continue
            label_arr = label.data if isinstance(label, nd.NDArray) else numpy.asarray(label)
            shape = pred_label.shape
            n_lab = int(numpy.prod(label_arr.shape))
            if n_lab != shape[0]:
                raise ValueError(
                    "Shape of labels %d does not match shape of predictions %d"
                    % (n_lab, shape[0])
                )
            if len(shape) == 1:
                k = 1
            else:
                k = min(shape[1], self.top_k)

            def count(acc, p, l, _k=k, _flat=len(shape) == 1):
                import jax.numpy as jnp
                from jax import lax

                if _flat:
                    hits = jnp.ravel(p).astype(jnp.int32) == jnp.ravel(l).astype(jnp.int32)
                else:
                    _, top_ids = lax.top_k(p.astype(jnp.float32), _k)
                    hits = jnp.any(
                        top_ids.astype(jnp.int32)
                        == jnp.ravel(l).astype(jnp.int32)[:, None], axis=1,
                    )
                return acc + jnp.sum(hits).astype(jnp.int32)

            self._accumulate(
                ("topk", k, shape, tuple(label_arr.shape)),
                count, pred_label.data, label_arr,
            )
            self.num_inst += int(shape[0])

    def _update_host(self, label, pred_label):
        pred_np = numpy.asarray(pred_label).astype("float32")
        label_np = _as_numpy(label).astype("int32")
        num_samples = pred_np.shape[0]
        if pred_np.ndim == 1:
            # 1-D predictions are class ids — the same semantic as the
            # device path (argsort with axis=1 would raise here)
            self.sum_metric += (
                pred_np.astype("int32").flat == label_np.flat
            ).sum()
        else:
            pred_np = numpy.argsort(pred_np, axis=1)
            num_classes = pred_np.shape[1]
            top_k = min(num_classes, self.top_k)
            for j in range(top_k):
                self.sum_metric += (
                    pred_np[:, num_classes - 1 - j].flat == label_np.flat
                ).sum()
        self.num_inst += num_samples


class F1(EvalMetric):
    """Binary F1 (reference: metric.py:461)."""

    def __init__(self, name="f1"):
        super().__init__(name)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            pred_np = pred.asnumpy()
            label_np = label.asnumpy().astype("int32")
            pred_label = numpy.argmax(pred_np, axis=1)
            check_label_shapes(label_np, pred_label)
            if len(numpy.unique(label_np)) > 2:
                raise ValueError("F1 currently only supports binary classification.")
            true_positives, false_positives, false_negatives = 0.0, 0.0, 0.0
            for y_pred, y_true in zip(pred_label, label_np):
                if y_pred == 1 and y_true == 1:
                    true_positives += 1.0
                elif y_pred == 1 and y_true == 0:
                    false_positives += 1.0
                elif y_pred == 0 and y_true == 1:
                    false_negatives += 1.0
            if true_positives + false_positives > 0:
                precision = true_positives / (true_positives + false_positives)
            else:
                precision = 0.0
            if true_positives + false_negatives > 0:
                recall = true_positives / (true_positives + false_negatives)
            else:
                recall = 0.0
            if precision + recall > 0:
                f1_score = 2 * precision * recall / (precision + recall)
            else:
                f1_score = 0.0
            self.sum_metric += f1_score
            self.num_inst += 1


class Perplexity(EvalMetric):
    """exp(avg NLL) (reference: metric.py:556).

    TPU-native accumulation (same rationale as _DeferredCountMetric): the
    per-batch statistic pair [exp(nll/n)*n, n] is computed by one jitted
    program ON DEVICE and chained into a device-resident 2-vector through a
    donated argument — the reference's eager path would pull the full
    softmax (batch*seq, vocab) to the host every batch, which on a
    high-latency transport costs more than the training step itself
    (measured: the LSTM-LM fit's batch time was dominated by this fetch).
    ``get()`` folds with a single 2-float fetch. Host/numpy preds keep the
    reference's eager path; batch-level averaging semantics (exp of the
    per-update mean, weighted by token count) are identical in both."""

    def __init__(self, ignore_label, axis=-1, name="Perplexity"):
        super().__init__(name)
        self.ignore_label = ignore_label
        self.axis = axis
        self._dev_acc = {}  # device-set -> [exp-weighted sum, token count]
        self._stat_fns = {}

    def reset(self):
        super().reset()
        self._dev_acc = {}

    def _flush(self):
        for acc in self._dev_acc.values():
            pair = numpy.asarray(acc)
            self.sum_metric += float(pair[0])
            self.num_inst += int(pair[1])
        self._dev_acc = {}

    def update(self, labels, preds):
        from . import ndarray as nd

        assert len(labels) == len(preds)
        # the reference applies exp ONCE over the whole update (loss and
        # token counts summed across all label/pred pairs first); split the
        # pairs by placement, run each side's accumulation, then combine
        host_pairs = []
        dev_pairs = []
        for label, pred in zip(labels, preds):
            assert label.size == pred.size / pred.shape[-1], (
                "shape mismatch: %s vs. %s" % (label.shape, pred.shape)
            )
            if isinstance(pred, nd.NDArray) and not all(
                    d.platform == "cpu" for d in pred.data.devices()):
                dev_pairs.append((label, pred))
            else:
                host_pairs.append((label, pred))
        if host_pairs:
            self._update_host(host_pairs)
        if dev_pairs:
            self._update_device(dev_pairs)

    def _update_host(self, pairs):
        loss = 0.0
        num = 0
        for label, pred in pairs:
            label_np = _as_numpy(label).astype("int32").reshape(-1)
            pred_np = _as_numpy(pred).reshape(-1, pred.shape[-1])
            probs = pred_np[numpy.arange(label_np.shape[0]), label_np]
            if self.ignore_label is not None:
                ignore = (label_np == self.ignore_label).astype(pred_np.dtype)
                num -= int(numpy.sum(ignore))
                probs = probs * (1 - ignore) + ignore
            loss -= numpy.sum(numpy.log(numpy.maximum(1e-10, probs)))
            num += label_np.shape[0]
        self.sum_metric += math.exp(loss / max(num, 1)) * max(num, 1)
        self.num_inst += max(num, 1)

    def _update_device(self, pairs):
        import jax

        from . import ndarray as nd

        # one jitted program per (shape-tuple, device-set): computes every
        # pair's nll/count, applies exp over the UPDATE's totals (reference
        # semantics), and chains the [exp(nll/n)*n, n] pair through a
        # donated accumulator. Per-device-set accumulators like
        # _DeferredCountMetric (executor groups emit per-device outputs).
        ref = pairs[0][1].data
        dev_key = frozenset(ref.devices())
        arrays = []
        shapes = []
        for label, pred in pairs:
            label_arr = label.data if isinstance(label, nd.NDArray) \
                else numpy.asarray(label)
            if hasattr(label_arr, "devices") \
                    and label_arr.devices() != pred.data.devices():
                # host-side label: local copy, jit re-places it beside the
                # predictions (async) — same rule as _DeferredCountMetric
                label_arr = numpy.asarray(label_arr)
            arrays.extend([pred.data, label_arr])
            shapes.append(tuple(pred.shape))
        key = (tuple(shapes), self.ignore_label, dev_key)
        fn = self._stat_fns.get(key)
        if fn is None:
            ignore_label = self.ignore_label

            def stat(acc, *flat):
                import jax.numpy as jnp

                nll = 0.0
                n = 0.0
                for i in range(0, len(flat), 2):
                    p, l = flat[i], flat[i + 1]
                    lab = jnp.ravel(l).astype(jnp.int32)
                    pr = p.reshape(-1, p.shape[-1])
                    probs = jnp.take_along_axis(
                        pr, lab[:, None], axis=1)[:, 0]
                    cnt = lab.shape[0]
                    if ignore_label is not None:
                        ign = (lab == int(ignore_label))
                        cnt = cnt - jnp.sum(ign)
                        probs = jnp.where(ign, 1.0, probs)
                    nll = nll - jnp.sum(
                        jnp.log(jnp.maximum(1e-10, probs)))
                    n = n + cnt
                n = jnp.maximum(n, 1).astype(jnp.float32)
                return acc + jnp.stack([jnp.exp(nll / n) * n, n])

            from . import compileobs

            fn = compileobs.jit(
                stat, "metric.perplexity",
                site="mxnet_tpu/metric.py:Perplexity.update",
                graph_key=key, donate_argnums=(0,))
            self._stat_fns[key] = fn
        acc = self._dev_acc.get(dev_key)
        if acc is None:
            acc = jax.device_put(numpy.zeros(2, numpy.float32),
                                 next(iter(ref.devices())))
        self._dev_acc[dev_key] = fn(acc, *arrays)

    def get(self):
        self._flush()
        return (self.name, self.sum_metric / self.num_inst if self.num_inst else float("nan"))


class MAE(EvalMetric):
    """(reference: metric.py:661)"""

    def __init__(self, name="mae"):
        super().__init__(name)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            self.sum_metric += numpy.abs(label_np - pred_np).mean()
            self.num_inst += 1


class MSE(EvalMetric):
    """(reference: metric.py:700)"""

    def __init__(self, name="mse"):
        super().__init__(name)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            self.sum_metric += ((label_np - pred_np) ** 2.0).mean()
            self.num_inst += 1


class RMSE(EvalMetric):
    """(reference: metric.py:739)"""

    def __init__(self, name="rmse"):
        super().__init__(name)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            self.sum_metric += numpy.sqrt(((label_np - pred_np) ** 2.0).mean())
            self.num_inst += 1


class CrossEntropy(EvalMetric):
    """(reference: metric.py:837)"""

    def __init__(self, eps=1e-12, name="cross-entropy"):
        super().__init__(name)
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            label_np = label_np.ravel()
            assert label_np.shape[0] == pred_np.shape[0]
            prob = pred_np[numpy.arange(label_np.shape[0]), numpy.int64(label_np)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += label_np.shape[0]


class Loss(EvalMetric):
    """Mean of raw outputs — for MakeLoss nets (reference: metric.py:901)."""

    def __init__(self, name="loss"):
        super().__init__(name)

    def update(self, _, preds):
        for pred in preds:
            self.sum_metric += numpy.sum(pred.asnumpy())
            self.num_inst += pred.size


class Torch(Loss):
    def __init__(self, name="torch"):
        super().__init__(name)


class Caffe(Loss):
    def __init__(self, name="caffe"):
        super().__init__(name)


class CustomMetric(EvalMetric):
    """Wrap a feval(label, pred) function (reference: metric.py:945)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label_np = label.asnumpy()
            pred_np = pred.asnumpy()
            reval = self._feval(label_np, pred_np)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


class MApMetric(EvalMetric):
    """Mean average precision for detection, VOC-style.

    (Reference: example/ssd/evaluate/eval_metric.py MApMetric — same
    update contract and matching protocol.)

    ``update(labels, preds)``:

    * ``labels[0]``: ``(batch, max_objects, >=5)`` ground truth, rows
      ``[cls, x0, y0, x1, y1, (difficult)]``, ``cls < 0`` = padding —
      exactly what ``ImageDetRecordIter`` emits;
    * ``preds[pred_idx]``: ``(batch, num_dets, 6)`` rows
      ``[cls, score, x0, y0, x1, y1]`` — ``MultiBoxDetection`` output,
      ``cls < 0`` = suppressed.

    Per-class AP uses VOC07 11-point interpolation by default
    (``voc07=False`` switches to all-points precision-envelope
    integration). With ``class_names``, ``get()`` returns each class AP
    plus the mean; otherwise just the mean.
    """

    def __init__(self, ovp_thresh=0.5, use_difficult=False,
                 class_names=None, pred_idx=0, voc07=True,
                 score_thresh=0.0):
        self.ovp_thresh = float(ovp_thresh)
        self.use_difficult = bool(use_difficult)
        self.class_names = list(class_names) if class_names else None
        self.pred_idx = int(pred_idx)
        self.voc07 = bool(voc07)
        self.score_thresh = float(score_thresh)
        super().__init__("mAP")

    def reset(self):
        # per class: list of (score, is_tp); ground-truth count
        self._records = {}
        self._npos = {}
        self._img = 0
        self.num_inst = 0
        self.sum_metric = 0.0

    def update(self, labels, preds):
        gts = _as_numpy(labels[0])
        dets = _as_numpy(preds[self.pred_idx])
        for i in range(gts.shape[0]):
            gt = gts[i][gts[i, :, 0] >= 0]
            difficult = (gt[:, 5] > 0 if gt.shape[1] > 5
                         else numpy.zeros(gt.shape[0], bool))
            if self.use_difficult:
                difficult = numpy.zeros(gt.shape[0], bool)
            for c in numpy.unique(gt[:, 0]).astype(int):
                mask = gt[:, 0] == c
                self._npos[c] = (self._npos.get(c, 0)
                                 + int((mask & ~difficult).sum()))
            det = dets[i][(dets[i, :, 0] >= 0)
                          & (dets[i, :, 1] >= self.score_thresh)]
            # VOC protocol: each detection (best score first) matches its
            # HIGHEST-IoU same-class gt; a second match of a taken gt is a
            # false positive, not a match of the next-best gt
            taken = numpy.zeros(gt.shape[0], bool)
            for row in det[numpy.argsort(-det[:, 1])]:
                c = int(row[0])
                cand = numpy.where(gt[:, 0] == c)[0]
                best_iou, best_j = 0.0, -1
                if cand.size:
                    g = gt[cand]
                    iw = (numpy.minimum(row[4], g[:, 3])
                          - numpy.maximum(row[2], g[:, 1]))
                    ih = (numpy.minimum(row[5], g[:, 4])
                          - numpy.maximum(row[3], g[:, 2]))
                    inter = numpy.maximum(iw, 0.0) * numpy.maximum(ih, 0.0)
                    union = ((row[4] - row[2]) * (row[5] - row[3])
                             + (g[:, 3] - g[:, 1]) * (g[:, 4] - g[:, 2])
                             - inter)
                    iou = numpy.where(union > 0, inter / union, 0.0)
                    k = int(iou.argmax())
                    best_iou, best_j = float(iou[k]), int(cand[k])
                rec = self._records.setdefault(c, [])
                if best_j >= 0 and best_iou >= self.ovp_thresh:
                    if difficult[best_j]:
                        continue  # matched a difficult gt: ignore entirely
                    if taken[best_j]:
                        rec.append((float(row[1]), 0))  # duplicate: FP
                    else:
                        taken[best_j] = True
                        rec.append((float(row[1]), 1))
                else:
                    rec.append((float(row[1]), 0))
            self._img += 1
        self.num_inst = self._img

    def _class_ap(self, c):
        npos = self._npos.get(c, 0)
        if npos == 0:
            return float("nan")
        rec = sorted(self._records.get(c, []), key=lambda r: -r[0])
        tp = numpy.cumsum([r[1] for r in rec]) if rec else numpy.zeros(0)
        n = numpy.arange(1, len(rec) + 1)
        recall = tp / npos if len(rec) else numpy.zeros(0)
        precision = tp / n if len(rec) else numpy.zeros(0)
        if self.voc07:
            ap = 0.0
            for k in range(11):
                # t - 1e-9: recall==k/10 computed as tp/npos must not miss
                # its own threshold to float error
                hit = recall >= (k / 10.0 - 1e-9)
                ap += (precision[hit].max() if hit.any() else 0.0) / 11.0
            return float(ap)
        # all-points: integrate the precision envelope over recall
        mrec = numpy.concatenate([[0.0], recall, [1.0]])
        mpre = numpy.concatenate([[0.0], precision, [0.0]])
        for k in range(len(mpre) - 2, -1, -1):
            mpre[k] = max(mpre[k], mpre[k + 1])
        idx = numpy.where(mrec[1:] != mrec[:-1])[0]
        return float(numpy.sum((mrec[idx + 1] - mrec[idx]) * mpre[idx + 1]))

    def get(self):
        classes = sorted(self._npos)
        aps = [self._class_ap(c) for c in classes]
        mean = (float(numpy.nanmean(aps))
                if aps and not all(math.isnan(a) for a in aps)
                else float("nan"))
        if self.class_names is None:
            return (self.name, mean)
        by_c = dict(zip(classes, aps))
        names = self.class_names + ["mAP"]
        values = [by_c.get(i, float("nan"))
                  for i in range(len(self.class_names))] + [mean]
        return (names, values)


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Make a CustomMetric from a numpy feval (reference: metric.py:1025)."""

    def feval(label, pred):
        return numpy_feval(label, pred)

    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def create(metric, **kwargs):
    """Create by name or callable (reference: metric.py:148)."""
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite_metric = CompositeEvalMetric()
        for child_metric in metric:
            composite_metric.add(create(child_metric, **kwargs))
        return composite_metric
    metrics = {
        "acc": Accuracy, "accuracy": Accuracy, "ce": CrossEntropy,
        "f1": F1, "mae": MAE, "mse": MSE, "rmse": RMSE,
        "top_k_accuracy": TopKAccuracy, "topkaccuracy": TopKAccuracy,
        "perplexity": Perplexity, "loss": Loss, "torch": Torch, "caffe": Caffe,
        "map": MApMetric, "mapmetric": MApMetric,
    }
    try:
        return metrics[metric.lower()](**kwargs)
    except Exception:
        raise ValueError("Metric must be either callable or in {}".format(sorted(metrics.keys())))
