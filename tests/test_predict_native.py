"""Python-free deployment tests: the `.mxa` AOT artifact + PJRT native
predict library (mxnet_tpu/export_artifact.py + src/c_predict_pjrt.cc —
the analog of the reference's amalgamation/c_predict_api deployment stack,
amalgamation/README.md:1-13, src/c_api/c_predict_api.cc:1).

The headline assertion: a compiled **C** client (tests/c/
predict_native_client.c) whose process never loads Python runs a model
exported by this framework on a PJRT device and matches the Python
executor's outputs. `ldd` on the library is asserted libpython-free.

These tests need a PJRT plugin: they use MXTPU_PJRT_PLUGIN if set and skip
otherwise — mirroring how the reference's amalgamation tests need a device
to run against.
"""
import os
import shutil
import struct
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
    exe = str(tmp_path / "pnc")
    r = subprocess.run(
        ["gcc", "-O2", "-o", exe,
         os.path.join(ROOT, "tests", "c", "predict_native_client.c"),
         "-L", os.path.dirname(lib), "-lmxtpu_predict_native",
         "-Wl,-rpath," + os.path.dirname(lib)],
        capture_output=True, text=True)
    if r.returncode != 0:
        pytest.fail("client build failed: %s" % r.stderr[-800:])
    return exe


def _mlp_and_params():
    import mxnet_tpu as mx
    rs = np.random.RandomState(7)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    args = {"fc1_weight": rs.randn(16, 8).astype(np.float32) * 0.1,
            "fc1_bias": rs.randn(16).astype(np.float32) * 0.01,
            "fc2_weight": rs.randn(4, 16).astype(np.float32) * 0.1,
            "fc2_bias": np.zeros(4, np.float32)}
    return net, args


def test_ldd_shows_no_libpython():
    lib = _build_lib()
    out = subprocess.run(["ldd", lib], capture_output=True,
                         text=True).stdout.lower()
    assert "python" not in out, "native predict lib links Python:\n" + out


def test_artifact_container_roundtrip(tmp_path):
    import mxnet_tpu as mx
    net, args = _mlp_and_params()
    path = str(tmp_path / "mlp.mxa")
    manifest = mx.export_predict_artifact(net, args, {}, {"data": (2, 8)},
                                          path, platform="cpu")
    assert [i["name"] for i in manifest["inputs"]] == ["data",
                                                       "softmax_label"]
    assert manifest["inputs"][1]["kind"] == "label"
    assert manifest["params"] == ["arg:fc1_weight", "arg:fc1_bias",
                                  "arg:fc2_weight", "arg:fc2_bias"]
    m2, plen, qlen = mx.export_artifact.load_artifact_manifest(path)
    assert m2 == manifest and plen > 0 and qlen > 0
    # magic + sizes add up to the file
    sz = os.path.getsize(path)
    with open(path, "rb") as f:
        f.seek(8)
        (mlen,) = struct.unpack("<Q", f.read(8))
    assert sz == 8 + 8 + mlen + 8 + plen + 8 + qlen


@needs_toolchain
def test_c_client_matches_python_executor(tmp_path):
    """A pure-C process runs the artifact on the PJRT device and matches
    the Python executor to 1e-5 (VERDICT round-3 'Done' criterion)."""
    env = _plugin_env()
    import mxnet_tpu as mx
    exe = _build_client(tmp_path)
    net, args = _mlp_and_params()
    path = str(tmp_path / "mlp.mxa")
    mx.export_predict_artifact(net, args, {}, {"data": (2, 8)}, path,
                               platform="tpu")

    rs = np.random.RandomState(3)
    x = rs.randn(2, 8).astype(np.float32)
    x.tofile(str(tmp_path / "in.f32"))
    ex = net.simple_bind(mx.cpu(), data=(2, 8), softmax_label=(2,),
                         grad_req="null")
    for k, v in args.items():
        ex.arg_dict[k][:] = v
    ex.arg_dict["data"][:] = x
    ref = ex.forward(is_train=False)[0].asnumpy()

    r = subprocess.run([exe, path, "data", str(tmp_path / "in.f32"),
                        str(tmp_path / "out.f32")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr
    out = np.fromfile(str(tmp_path / "out.f32"), np.float32).reshape(2, 4)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@needs_toolchain
def test_c_client_output_layout(tmp_path):
    """Regression: on TPU the compiler may pick a column-major output
    layout (observed for a (16, 2) softmax); MXPredGetOutput must request a
    row-major host layout, not copy the device layout verbatim."""
    env = _plugin_env()
    import mxnet_tpu as mx
    exe = _build_client(tmp_path)
    rs = np.random.RandomState(19)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    args = {"fc1_weight": rs.randn(8, 10).astype(np.float32),
            "fc1_bias": rs.randn(8).astype(np.float32),
            "fc2_weight": rs.randn(2, 8).astype(np.float32),
            "fc2_bias": rs.randn(2).astype(np.float32)}
    path = str(tmp_path / "m.mxa")
    mx.export_predict_artifact(net, args, {}, {"data": (16, 10)}, path,
                               platform="tpu")
    x = rs.randn(16, 10).astype(np.float32)
    x.tofile(str(tmp_path / "in.f32"))
    ex = net.simple_bind(mx.cpu(), data=(16, 10), softmax_label=(16,),
                         grad_req="null")
    for k, v in args.items():
        ex.arg_dict[k][:] = v
    ex.arg_dict["data"][:] = x
    ref = ex.forward(is_train=False)[0].asnumpy()
    r = subprocess.run([exe, path, "data", str(tmp_path / "in.f32"),
                        str(tmp_path / "out.f32")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr
    out = np.fromfile(str(tmp_path / "out.f32"), np.float32).reshape(16, 2)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@needs_toolchain
def test_c_client_conv_net(tmp_path):
    """Conv/pool/batchnorm path through the native runtime (MXU lowering on
    TPU; exercises aux-state params in the artifact)."""
    env = _plugin_env()
    import mxnet_tpu as mx
    exe = _build_client(tmp_path)
    rs = np.random.RandomState(11)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    shapes = {"conv1_weight": (8, 1, 3, 3), "conv1_bias": (8,),
              "bn1_gamma": (8,), "bn1_beta": (8,),
              "fc_weight": (3, 8 * 7 * 7), "fc_bias": (3,)}
    args = {k: (rs.randn(*v).astype(np.float32) * 0.2) for k, v in
            shapes.items()}
    aux = {"bn1_moving_mean": rs.randn(8).astype(np.float32) * 0.1,
           "bn1_moving_var": (1 + 0.1 * rs.rand(8)).astype(np.float32)}
    path = str(tmp_path / "conv.mxa")
    mx.export_predict_artifact(net, args, aux, {"data": (2, 1, 14, 14)},
                               path, platform="tpu")

    x = rs.randn(2, 1, 14, 14).astype(np.float32)
    x.tofile(str(tmp_path / "in.f32"))
    ex = net.simple_bind(mx.cpu(), data=(2, 1, 14, 14), softmax_label=(2,),
                         grad_req="null")
    for k, v in args.items():
        ex.arg_dict[k][:] = v
    for k, v in aux.items():
        ex.aux_dict[k][:] = v
    ex.arg_dict["data"][:] = x
    ref = ex.forward(is_train=False)[0].asnumpy()

    r = subprocess.run([exe, path, "data", str(tmp_path / "in.f32"),
                        str(tmp_path / "out.f32")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, "client failed:\n" + r.stdout + r.stderr
    out = np.fromfile(str(tmp_path / "out.f32"), np.float32).reshape(2, 3)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@needs_toolchain
def test_shape_validation_and_ndlist(tmp_path):
    """MXPredCreate rejects caller shapes that differ from the AOT artifact;
    MXNDListCreate parses a .params blob in pure C++."""
    env = _plugin_env()
    lib = _build_lib()
    import mxnet_tpu as mx
    net, args = _mlp_and_params()
    path = str(tmp_path / "mlp.mxa")
    mx.export_predict_artifact(net, args, {}, {"data": (2, 8)}, path,
                               platform="tpu")
    params_path = str(tmp_path / "p.params")
    mx.nd.save(params_path, {k: mx.nd.array(v) for k, v in args.items()})

    src = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
typedef unsigned int mx_uint;
typedef void* PredictorHandle;
typedef void* NDListHandle;
extern const char* MXGetLastError(void);
extern int MXPredCreate(const char*, const void*, int, int, int, mx_uint,
                        const char**, const mx_uint*, const mx_uint*,
                        PredictorHandle*);
extern int MXNDListCreate(const char*, int, NDListHandle*, mx_uint*);
extern int MXNDListGet(NDListHandle, mx_uint, const char**, const float**,
                       const mx_uint**, mx_uint*);
extern int MXNDListFree(NDListHandle);
static void* slurp(const char* p, long* n) {
  FILE* f = fopen(p, "rb"); fseek(f, 0, SEEK_END); *n = ftell(f);
  fseek(f, 0, SEEK_SET); void* b = malloc(*n);
  if (fread(b, 1, *n, f) != (size_t)*n) exit(2); fclose(f); return b;
}
int main(int argc, char** argv) {
  (void)argc;
  long an = 0, pn = 0;
  void* art = slurp(argv[1], &an);
  void* prm = slurp(argv[2], &pn);
  /* wrong shape must fail with a clear message */
  const char* keys[1] = {"data"};
  mx_uint indptr[2] = {0, 2};
  mx_uint dims[2] = {4, 8};  /* artifact says (2, 8) */
  PredictorHandle h = NULL;
  if (MXPredCreate(NULL, art, (int)an, 6, 0, 1, keys, indptr, dims, &h) == 0) {
    fprintf(stderr, "shape mismatch accepted!\n"); return 1;
  }
  if (!strstr(MXGetLastError(), "re-export")) {
    fprintf(stderr, "unexpected error: %s\n", MXGetLastError()); return 1;
  }
  /* NDList parses the .params wire format without Python */
  NDListHandle lst = NULL; mx_uint len = 0;
  if (MXNDListCreate((const char*)prm, (int)pn, &lst, &len) != 0) {
    fprintf(stderr, "ndlist: %s\n", MXGetLastError()); return 1;
  }
  if (len != 4) { fprintf(stderr, "len=%u\n", len); return 1; }
  mx_uint found = 0;
  for (mx_uint i = 0; i < len; ++i) {
    const char* key; const float* data; const mx_uint* shp; mx_uint nd;
    if (MXNDListGet(lst, i, &key, &data, &shp, &nd) != 0) return 1;
    if (strcmp(key, "fc1_weight") == 0 && nd == 2 && shp[0] == 16 &&
        shp[1] == 8) found = 1;
  }
  MXNDListFree(lst);
  if (!found) { fprintf(stderr, "fc1_weight not found\n"); return 1; }
  printf("OK\n");
  return 0;
}
"""
    csrc = tmp_path / "check.c"
    csrc.write_text(src)
    exe = str(tmp_path / "check")
    r = subprocess.run(["gcc", "-O2", "-o", exe, str(csrc),
                        "-L", os.path.dirname(lib),
                        "-lmxtpu_predict_native",
                        "-Wl,-rpath," + os.path.dirname(lib)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    r = subprocess.run([exe, path, params_path], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr


def test_export_rejects_missing_params(tmp_path):
    """A forgotten weight must fail the export, not become a zero-fed
    'label' input (silently wrong artifact)."""
    import mxnet_tpu as mx
    net, args = _mlp_and_params()
    del args["fc1_bias"]
    with pytest.raises(mx.MXNetError, match="fc1_bias"):
        mx.export_predict_artifact(net, args, {}, {"data": (2, 8)},
                                   str(tmp_path / "x.mxa"), platform="cpu")
