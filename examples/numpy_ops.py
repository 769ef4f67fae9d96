"""Custom python operators (reference: example/numpy-ops/{custom_softmax,
numpy_softmax,weighted_logistic_regression}.py — implement an op's forward
AND backward in numpy via CustomOp/CustomOpProp, register it, and train a
net that uses it like any built-in).

The numpy softmax-with-CE-loss head (the reference's canonical example) is
implemented with forward AND backward in numpy. Custom python ops execute on
the HOST — inside a device graph they become host callbacks, so this example
keeps the whole model on CPU (the reference's NumpyOp likewise ran CPU-side
even in GPU models; transports without host-callback support can't run them
in-device at all).
"""
import argparse
import logging

import numpy as np

import mxnet_tpu as mx


@mx.operator.register("numpy_softmax")
class NumpySoftmaxProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shape):
        data_shape = in_shape[0]
        label_shape = (in_shape[0][0],)
        return [data_shape, label_shape], [data_shape], []

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return NumpySoftmax()


class NumpySoftmax(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = np.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)
        self.assign(out_data[0], req[0], mx.nd.array(y))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        label = in_data[1].asnumpy().astype(int).ravel()
        y = out_data[0].asnumpy().copy()
        y[np.arange(len(label)), label] -= 1.0
        self.assign(in_grad[0], req[0], mx.nd.array(y / len(label)))
        self.assign(in_grad[1], req[1], mx.nd.zeros(in_grad[1].shape))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--num-epoch", type=int, default=5)
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO)

    rng = np.random.RandomState(0)
    templates = (rng.rand(10, 784) > 0.7).astype(np.float32)
    label = rng.randint(0, 10, 4096)
    data = (templates[label] + 0.3 * rng.randn(4096, 784)).astype(np.float32)
    label = label.astype(np.float32)

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=128)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=10)
    lab = mx.sym.Variable("softmax_label")
    net = mx.sym.Custom(data=net, label=lab, op_type="numpy_softmax",
                        name="softmax")

    train = mx.io.NDArrayIter(data[:3584], label[:3584], args.batch_size,
                              shuffle=True)
    val = mx.io.NDArrayIter(data[3584:], label[3584:], args.batch_size)
    # custom python ops run as host callbacks inside the compiled step; the
    # CPU context keeps the whole graph host-side — the reference's NumpyOp
    # was likewise CPU-executed even in GPU models
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train, eval_data=val, eval_metric="acc",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            num_epoch=args.num_epoch,
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 20))
    logging.info("final validation %s", mod.score(val, mx.metric.create("acc")))


if __name__ == "__main__":
    main()
