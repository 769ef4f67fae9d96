"""Python-free TRAINING tests: kind="train" `.mxa` artifacts + the
MXTrainNative* PJRT runtime (mxnet_tpu/export_artifact.py
export_train_artifact + src/c_predict_pjrt.cc).

This goes beyond the reference's deployment stack — its amalgamation /
c_predict_api ran inference only (amalgamation/README.md:1-13,
src/c_api/c_predict_api.cc:1); here the exported program is the fused
training step (forward + backward + optimizer update, the same trace
Module.fit's fused path runs), so a pure-C process TRAINS on the PJRT
device and hands back a reference-format `.params` checkpoint.

Headline assertions:
  * a compiled C client (tests/c/train_native_client.c) trains an MLP to
    >90% train accuracy from scratch — no Python in that process;
  * the first native steps match SPMDTrainer.step numerically;
  * the saved checkpoint loads into the Python Module path.

Needs a PJRT plugin (same gating as test_predict_native.py).
"""
import os
import shutil
import subprocess

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "mxnet_tpu", "src")

needs_toolchain = pytest.mark.skipif(shutil.which("gcc") is None,
                                     reason="no C toolchain")


def _plugin_env():
    if not os.environ.get("MXTPU_PJRT_PLUGIN"):
        pytest.skip("no PJRT plugin given (set MXTPU_PJRT_PLUGIN)")
    return dict(os.environ)


def _build_lib():
    r = subprocess.run(["make", "c_predict_native"], cwd=SRC,
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.fail("native predict build failed: %s" % r.stderr[-800:])
    return os.path.join(SRC, "build", "libmxtpu_predict_native.so")


def _build_client(tmp_path):
    lib = _build_lib()
    exe = str(tmp_path / "tnc")
    r = subprocess.run(
        ["gcc", "-O2", "-o", exe,
         os.path.join(ROOT, "tests", "c", "train_native_client.c"),
         "-L", os.path.dirname(lib), "-lmxtpu_predict_native",
         "-lm", "-Wl,-rpath," + os.path.dirname(lib)],
        capture_output=True, text=True)
    if r.returncode != 0:
        pytest.fail("client build failed: %s" % r.stderr[-800:])
    return exe


def _mlp():
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    return net


def _three_class_data(n, seed=5):
    """Linearly separable 3-class blobs in 8-D."""
    rs = np.random.RandomState(seed)
    centers = rs.randn(3, 8).astype(np.float32) * 3
    y = np.arange(n) % 3
    x = centers[y] + rs.randn(n, 8).astype(np.float32)
    return x.astype(np.float32), y.astype(np.float32)


def test_manifest_and_container(tmp_path):
    import mxnet_tpu as mx
    net = _mlp()
    path = str(tmp_path / "t.mxa")
    m = mx.export_train_artifact(
        net, {"data": (8, 8)}, path, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        platform="cpu")
    assert m["kind"] == "train" and m["nslot"] == 1
    roles = [a["role"] for a in m["args"]]
    # params, states, auxs(none), data, label, lr, t
    assert roles == ["param"] * 4 + ["state"] * 4 + ["data", "label",
                                                    "lr", "t"]
    out_roles = [o["role"] for o in m["outputs"]]
    assert out_roles == ["param"] * 4 + ["state"] * 4 + ["out"]
    assert m["loss_outputs"] == [True]
    # carry order: the carried prefix of outputs mirrors args by name
    n_carry = sum(r in ("param", "state", "aux") for r in roles)
    for a, o in zip(m["args"][:n_carry], m["outputs"][:n_carry]):
        assert a["name"] == o["name"]
    m2, plen, qlen = mx.export_artifact.load_artifact_manifest(path)
    assert m2 == m and plen > 0 and qlen > 0


@needs_toolchain
def test_c_client_trains_mlp(tmp_path):
    """A pure-C process trains the MLP to >90% train accuracy and its
    checkpoint round-trips into Python's Module."""
    env = _plugin_env()
    import mxnet_tpu as mx
    exe = _build_client(tmp_path)
    net = _mlp()
    batch = 32
    path = str(tmp_path / "mlp_train.mxa")
    mx.export_train_artifact(
        net, {"data": (batch, 8)}, path, optimizer="sgd",
        optimizer_params={"learning_rate": 0.02, "momentum": 0.9},
        platform="tpu", seed=3)

    x, y = _three_class_data(128)
    x.tofile(str(tmp_path / "data.f32"))
    y.tofile(str(tmp_path / "labels.f32"))
    params_out = str(tmp_path / "trained.params")
    loss_out = str(tmp_path / "loss.txt")
    r = subprocess.run(
        [exe, path, str(tmp_path / "data.f32"), str(tmp_path / "labels.f32"),
         str(batch), "400", "0.02", params_out, loss_out],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr

    # loss decreased by an order of magnitude
    losses = [float(l.split()[1]) for l in open(loss_out)]
    assert losses[-1] < losses[0] * 0.1, losses

    # checkpoint loads into the Python side and scores the training set
    save_dict = mx.nd.load(params_out)
    arg = {k[4:]: v for k, v in save_dict.items() if k.startswith("arg:")}
    aux = {k[4:]: v for k, v in save_dict.items() if k.startswith("aux:")}
    mod = mx.mod.Module(net, label_names=["softmax_label"],
                        context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 8))],
             label_shapes=[("softmax_label", (batch,))], for_training=False)
    mod.set_params(arg, aux, allow_missing=False)
    correct = 0
    for i in range(0, len(x), batch):
        b = mx.io.DataBatch(data=[mx.nd.array(x[i:i + batch])], label=[])
        mod.forward(b, is_train=False)
        pred = mod.get_outputs()[0].asnumpy().argmax(axis=1)
        correct += (pred == y[i:i + batch]).sum()
    acc = correct / len(x)
    assert acc > 0.9, "C-trained model scores %.3f" % acc


@needs_toolchain
def test_c_client_trains_bf16(tmp_path):
    """compute_dtype='bfloat16' bakes the mixed-precision recipe into the
    artifact: a pure-C process trains with bf16 compute + fp32 masters."""
    env = _plugin_env()
    import mxnet_tpu as mx
    exe = _build_client(tmp_path)
    net = _mlp()
    batch = 32
    path = str(tmp_path / "mlp_bf16.mxa")
    m = mx.export_train_artifact(
        net, {"data": (batch, 8)}, path, optimizer="sgd",
        optimizer_params={"learning_rate": 0.02, "momentum": 0.9},
        platform="tpu", seed=3, compute_dtype="bfloat16")
    assert m["compute_dtype"] == "bfloat16"
    # the C signature stays float32 everywhere
    assert all(a["dtype"] == "float32" for a in m["args"]
               if a["role"] != "t")

    x, y = _three_class_data(128)
    x.tofile(str(tmp_path / "data.f32"))
    y.tofile(str(tmp_path / "labels.f32"))
    params_out = str(tmp_path / "bf16.params")
    r = subprocess.run(
        [exe, path, str(tmp_path / "data.f32"), str(tmp_path / "labels.f32"),
         str(batch), "400", "0.02", params_out, str(tmp_path / "l.txt")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr
    losses = [float(l.split()[1]) for l in open(str(tmp_path / "l.txt"))]
    assert losses[-1] < losses[0] * 0.2, losses
    # fp32 master params round-trip
    sd = mx.nd.load(params_out)
    assert all(v.asnumpy().dtype == np.float32 for v in sd.values())


@needs_toolchain
def test_c_client_trains_conv_bn(tmp_path):
    """Aux-state carry through the native step: a conv+BatchNorm net's
    moving statistics must be UPDATED by C-side training (they ride the
    carry like params) and land in the saved checkpoint."""
    env = _plugin_env()
    import mxnet_tpu as mx
    exe = _build_client(tmp_path)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    batch = 16
    path = str(tmp_path / "convbn.mxa")
    m = mx.export_train_artifact(
        net, {"data": (batch, 1, 8, 8)}, path, optimizer="sgd",
        optimizer_params={"learning_rate": 0.02, "momentum": 0.9},
        platform="tpu", seed=1)
    assert any(a["role"] == "aux" for a in m["args"])

    x, ycls = _three_class_data(64, seed=4)
    # lift the 8-D blobs into 1x8x8 images (shifted copies fill the rows)
    xi = np.zeros((64, 1, 8, 8), np.float32)
    for r in range(8):
        xi[:, 0, r, :] = np.roll(x, r, axis=1)
    xi.tofile(str(tmp_path / "data.f32"))
    ycls.tofile(str(tmp_path / "labels.f32"))
    params_out = str(tmp_path / "convbn.params")
    r = subprocess.run(
        [exe, path, str(tmp_path / "data.f32"), str(tmp_path / "labels.f32"),
         str(batch), "300", "0.02", params_out, str(tmp_path / "l.txt")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr
    losses = [float(l.split()[1]) for l in open(str(tmp_path / "l.txt"))]
    assert losses[-1] < losses[0] * 0.5, losses

    sd = mx.nd.load(params_out)
    mean = sd["aux:bn1_moving_mean"].asnumpy()
    var = sd["aux:bn1_moving_var"].asnumpy()
    # moving stats moved off their init (mean 0 / var 1) => aux carry works
    assert np.abs(mean).max() > 1e-3, mean
    assert np.abs(var - 1.0).max() > 1e-3, var


@needs_toolchain
def test_native_steps_match_python_trainer(tmp_path):
    """The native step IS the fused step: three C steps from a fixed init
    match three SPMDTrainer.step calls on the same batches."""
    env = _plugin_env()
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import build_mesh
    from mxnet_tpu.parallel.spmd import SPMDTrainer

    exe = _build_client(tmp_path)
    net = _mlp()
    batch = 16
    rs = np.random.RandomState(0)
    init = {"fc1_weight": rs.randn(32, 8).astype(np.float32) * 0.3,
            "fc1_bias": np.zeros(32, np.float32),
            "fc2_weight": rs.randn(3, 32).astype(np.float32) * 0.3,
            "fc2_bias": np.zeros(3, np.float32)}
    path = str(tmp_path / "par.mxa")
    mx.export_train_artifact(
        net, {"data": (batch, 8)}, path, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        arg_params=init, platform="tpu")

    x, y = _three_class_data(batch * 1, seed=9)  # ONE batch, cycled 3 times
    x.tofile(str(tmp_path / "data.f32"))
    y.tofile(str(tmp_path / "labels.f32"))
    params_out = str(tmp_path / "p3.params")
    r = subprocess.run(
        [exe, path, str(tmp_path / "data.f32"), str(tmp_path / "labels.f32"),
         str(batch), "3", "0.05", params_out, str(tmp_path / "l.txt")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr

    # same three steps through SPMDTrainer on the CPU mesh
    with jax.default_matmul_precision("highest"):
        mesh = build_mesh({"dp": 1}, list(jax.devices("cpu"))[:1])
        tr = SPMDTrainer(net, mesh, data_shapes=[("data", (batch, 8))],
                         label_shapes=[("softmax_label", (batch,))],
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.05,
                                           "momentum": 0.9})
        params = {n: jax.device_put(init[n].astype(np.float32))
                  for n in tr.param_names}
        states = tr.init_opt_state()
        auxs = {}
        inputs = {"data": x, "softmax_label": y}
        for _ in range(3):
            params, auxs, states, _ = tr.step(params, auxs, states, inputs)

    got = {k[4:]: v.asnumpy() for k, v in mx.nd.load(params_out).items()
           if k.startswith("arg:")}
    for n in tr.param_names:
        np.testing.assert_allclose(got[n], np.asarray(params[n]),
                                   atol=5e-4, rtol=5e-4)


@needs_toolchain
def test_corrupt_mxa_shape_mismatch_fails_cleanly(tmp_path):
    """A crafted .mxa whose manifest shape exceeds the params-blob record
    must fail at create time with a clear error, not read past the record
    (the ndarray_wire.h 'corrupt files fail cleanly' invariant)."""
    import json
    import struct

    env = _plugin_env()
    import mxnet_tpu as mx
    exe = _build_client(tmp_path)
    net = _mlp()
    path = str(tmp_path / "ok.mxa")
    mx.export_train_artifact(
        net, {"data": (8, 8)}, path, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1}, platform="tpu")

    # rewrite the container with the first param's shape inflated 4x
    raw = open(path, "rb").read()
    assert raw[:8] == b"MXTPUAR1"
    (mlen,) = struct.unpack("<Q", raw[8:16])
    manifest = json.loads(raw[16:16 + mlen].decode())
    first_param = next(a for a in manifest["args"] if a["role"] == "param")
    first_param["shape"][0] *= 4
    mjs = json.dumps(manifest, indent=1).encode()
    bad = str(tmp_path / "bad.mxa")
    with open(bad, "wb") as f:
        f.write(raw[:8])
        f.write(struct.pack("<Q", len(mjs)))
        f.write(mjs)
        f.write(raw[16 + mlen:])

    x = np.zeros(64, np.float32)
    x.tofile(str(tmp_path / "d.f32"))
    x.tofile(str(tmp_path / "l.f32"))
    r = subprocess.run(
        [exe, bad, str(tmp_path / "d.f32"), str(tmp_path / "l.f32"),
         "8", "1", "0.1", str(tmp_path / "o.params"),
         str(tmp_path / "loss.txt")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "shape mismatch" in (r.stdout + r.stderr)
