"""Hardware test for the Python-free deployment path: ResNet-50 exported to
a `.mxa` artifact and run by a pure-C client on the real TPU, outputs
matching the Python executor (VERDICT round-3 criterion for the
amalgamation-analog: `src/c_api/c_predict_api.cc:1`,
`amalgamation/README.md:1-13`).

Runs in the TPU suite (`ci/run_tests.sh tpu`) where MXTPU_PJRT_PLUGIN names
a plugin: the parent uses jax for the export + reference, the C client talks
to the chip through the plugin with no Python in its process. A chip belongs
to one process and this suite's pytest parent holds it (conftest.py counts
the chips), so the client needs a second chip or a plugin that shares one:
on a one-chip machine this test cannot pass.
"""
import os
import subprocess

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resnet50_artifact_matches_python(tmp_path):
    if not os.environ.get("MXTPU_PJRT_PLUGIN"):
        pytest.skip("no PJRT plugin given (set MXTPU_PJRT_PLUGIN)")
    env = dict(os.environ)

    import mxnet_tpu as mx
    from mxnet_tpu import models

    src = os.path.join(ROOT, "mxnet_tpu", "src")
    r = subprocess.run(["make", "c_predict_native"], cwd=src,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-500:]
    lib_dir = os.path.join(src, "build")
    exe = str(tmp_path / "pnc")
    r = subprocess.run(
        ["gcc", "-O2", "-o", exe,
         os.path.join(ROOT, "tests", "c", "predict_native_client.c"),
         "-L", lib_dir, "-lmxtpu_predict_native", "-Wl,-rpath," + lib_dir],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    batch = 4
    net = models.resnet(num_classes=1000, num_layers=50,
                        image_shape="3,224,224")
    ex = net.simple_bind(mx.cpu(), data=(batch, 3, 224, 224),
                         softmax_label=(batch,), grad_req="null")
    rs = np.random.RandomState(0)
    arg_params, aux_params = {}, {}
    for k, v in ex.arg_dict.items():
        if k in ("data", "softmax_label"):
            continue
        arg_params[k] = (rs.randn(*v.shape) * 0.05).astype(np.float32)
        ex.arg_dict[k][:] = arg_params[k]
    for k, v in ex.aux_dict.items():
        if "var" in k:
            aux_params[k] = (1 + 0.05 * rs.rand(*v.shape)).astype(np.float32)
        else:
            aux_params[k] = (0.05 * rs.randn(*v.shape)).astype(np.float32)
        ex.aux_dict[k][:] = aux_params[k]

    path = str(tmp_path / "resnet50.mxa")
    mx.export_predict_artifact(net, arg_params, aux_params,
                               {"data": (batch, 3, 224, 224)}, path,
                               platform="tpu")

    x = rs.rand(batch, 3, 224, 224).astype(np.float32)
    x.tofile(str(tmp_path / "in.f32"))
    ex.arg_dict["data"][:] = x
    ref = ex.forward(is_train=False)[0].asnumpy()

    r = subprocess.run([exe, path, "data", str(tmp_path / "in.f32"),
                        str(tmp_path / "out.f32")],
                       capture_output=True, text=True, env=env, timeout=1200)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr
    out = np.fromfile(str(tmp_path / "out.f32"),
                      np.float32).reshape(batch, 1000)
    # fp32 HIGHEST-precision MXU vs CPU across ~50 conv layers
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)
