"""Inference throughput across the reference's benchmark models
(reference: docs/how_to/perf.md inference tables, measured by
example/image-classification/benchmark_score.py — batch 32, synthetic data
resident on device, timed forward only).

Prints one JSON line per model:
  {"model": ..., "imgs_per_sec": ..., "vs_p100": ...}
P100 fp32 batch-32 baselines from perf.md:140-147. Run with
MXNET_TPU_BENCH_DTYPE=float32 for the strict like-for-like fp32 comparison
(default bf16 is the TPU-native serving mode).
"""
import json
import os
import time

import numpy as np

P100_BASELINE = {  # img/s, batch 32, fp32 (docs/how_to/perf.md:140-147)
    "alexnet": 4883.77,
    "vgg16": 854.40,
    "inception-bn": 1197.74,
    "inception-v3": 493.72,
    "resnet-50": 713.17,
    "resnet-152": 294.17,
    # no published reference number for inception-resnet-v2 (perf.md omits it)
    "inception-resnet-v2": None,
}


def build(name, batch):
    from mxnet_tpu import models

    shape = ((batch, 3, 299, 299)
             if name in ("inception-v3", "inception-resnet-v2")
             else (batch, 3, 224, 224))
    if name == "alexnet":
        net = models.alexnet(num_classes=1000)
    elif name == "vgg16":
        net = models.vgg(num_classes=1000, num_layers=16)
    elif name == "inception-bn":
        net = models.inception_bn(num_classes=1000)
    elif name == "inception-v3":
        net = models.inception_v3(num_classes=1000)
    elif name == "inception-resnet-v2":
        net = models.inception_resnet_v2(num_classes=1000)
    elif name == "resnet-50":
        net = models.resnet(num_classes=1000, num_layers=50, image_shape="3,224,224")
    elif name == "resnet-152":
        net = models.resnet(num_classes=1000, num_layers=152, image_shape="3,224,224")
    else:
        raise ValueError(name)
    return net, shape


def bench_model(name, batch, steps, dtype):
    import jax

    from mxnet_tpu import initializer as init_mod
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.executor import build_graph_fn

    net, shape = build(name, batch)
    graph_fn, arg_names, aux_names = build_graph_fn(net)
    shapes = {"data": shape, "softmax_label": (batch,)}
    arg_shapes, _, aux_shapes = net.infer_shape(**shapes)
    init = init_mod.Xavier(rnd_type="gaussian", factor_type="in", magnitude=2)
    rng = np.random.RandomState(0)

    def make(nm, shp):
        if nm == "data":
            return jax.device_put(rng.rand(*shp).astype(dtype))
        if nm == "softmax_label":
            return jax.device_put(np.zeros(shp, np.float32))
        host = nd.zeros(shp)
        init(nm, host)
        return jax.device_put(host.asnumpy().astype(dtype))

    args = [make(n, s) for n, s in zip(arg_names, arg_shapes)]
    auxs = []
    for nm, shp in zip(aux_names, aux_shapes):
        host = nd.zeros(shp)
        init(nm, host)
        auxs.append(jax.device_put(host.asnumpy().astype(np.float32)))

    @jax.jit
    def fwd(args, auxs):
        outs, _ = graph_fn(args, auxs, None, False)
        return outs[0]

    out = fwd(args, auxs)
    np.asarray(out).ravel()[0]  # force compile + completion
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(args, auxs)
    np.asarray(out).ravel()[0]
    dt = time.perf_counter() - t0
    return steps * batch / dt


def main():
    batch = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "32"))
    steps = int(os.environ.get("MXNET_TPU_BENCH_STEPS", "50"))
    dtype_name = os.environ.get("MXNET_TPU_BENCH_DTYPE", "bfloat16")
    if dtype_name == "bfloat16":
        import jax.numpy as jnp

        dtype = np.dtype(jnp.bfloat16)
    else:
        dtype = np.dtype(np.float32)
    only = os.environ.get("MXNET_TPU_BENCH_MODELS")
    names = only.split(",") if only else list(P100_BASELINE)
    for name in names:
        ips = bench_model(name, batch, steps, dtype)
        base = P100_BASELINE.get(name)
        print(json.dumps({
            "model": name, "batch": batch, "dtype": dtype_name,
            "imgs_per_sec": round(ips, 2),
            "vs_p100": round(ips / base, 3) if base else None,
        }), flush=True)


if __name__ == "__main__":
    main()
