"""MATLAB binding tests (matlab/ — the analog of the reference's matlab
binding: +mxnet/model.m over c_predict_api.h / libmxnet_predict).

No MATLAB ships in this environment (and Octave, when present, lacks
loadlibrary/calllib), so the suite has three tiers:

1. **Static contract checks (always run):** every `callmxtpu(...)` C
   target in the .m files must be declared in `c_predict_api.h` with a
   matching argument count, and the classdef surface must keep the
   reference's methods (load/forward/parse_symbol).
2. **Sequence emulation (needs only the predict shim):** a subprocess
   ctypes driver replays the EXACT call sequence model.m performs —
   including the col-major→row-major permute/flatten and the output
   reshape — against a Python-trained conv checkpoint with H≠W, and the
   result must match Module.predict.  This pins the binding's data-layout
   contract without a MATLAB interpreter.
3. **Interpreter tier (gated):** Octave runs the pure-M parse_json test;
   MATLAB (if ever present) runs matlab/tests/test_prediction.m against
   fixtures this file generates.
"""
import os
import re
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "matlab")
SRC = os.path.join(ROOT, "mxnet_tpu", "src")
HEADER = os.path.join(SRC, "include", "c_predict_api.h")


def _m_sources():
    out = {}
    for dirpath, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".m"):
                p = os.path.join(dirpath, f)
                out[os.path.relpath(p, PKG)] = open(p).read()
    return out


def _count_top_level_args(text, start):
    """Count comma-separated args in a balanced (...) starting at start-1."""
    depth, args, any_tok = 1, 0, False
    i = start
    while depth > 0:
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and depth == 1:
            args += 1
        elif not c.isspace() and depth >= 1:
            any_tok = True
        i += 1
    return args + 1 if any_tok else 0


def _header_decls():
    """C function name -> parameter count from c_predict_api.h."""
    text = open(HEADER).read()
    decls = {}
    for m in re.finditer(r"int (MX\w+)\(([^;]*?)\);", text, re.S):
        name, params = m.group(1), m.group(2).strip()
        decls[name] = 0 if not params else params.count(",") + 1
    # MXGetLastError returns const char*, declared separately
    decls["MXGetLastError"] = 0
    return decls


def test_call_targets_exist_with_matching_arity():
    decls = _header_decls()
    found = []
    for rel, text in _m_sources().items():
        for m in re.finditer(r"callmxtpu\(\s*[\w.]+\s*,\s*'(MX\w+)'\s*,?\s*",
                             m_text := text):
            name = m.group(1)
            assert name in decls, "%s calls undeclared %s" % (rel, name)
            # args after (artifact, func) = the C function's params
            n = _count_top_level_args(m_text, m.start() +
                                      m_text[m.start():].index("(") + 1)
            assert n - 2 == decls[name], (
                "%s passes %d args to %s (header says %d)"
                % (rel, n - 2, name, decls[name]))
            found.append(name)
    assert set(found) >= {"MXPredCreatePartialOut", "MXPredSetInput",
                          "MXPredForward", "MXPredGetOutputShape",
                          "MXPredGetOutput", "MXPredFree"}


def test_classdef_keeps_reference_surface():
    text = _m_sources()["+mxnettpu/model.m"]
    for method in ("function obj = model", "function load(",
                   "function load_artifact(", "function json = parse_symbol",
                   "function outputs = forward"):
        assert method in text, "model.m lost method: %s" % method
    # the error path must surface MXGetLastError (via callmxtpu)
    helper = _m_sources()["+mxnettpu/private/callmxtpu.m"]
    assert "MXGetLastError" in helper


def test_demo_and_readme_reference_real_entry_points():
    demo = _m_sources()["demo.m"]
    assert "mxnettpu.model" in demo and "load_artifact" in demo
    readme = open(os.path.join(PKG, "README.md")).read()
    assert "c_predict_native" in readme and "MXNETTPU_LIB_DIR" in readme


# ---------------------------------------------------------------------------
# Tier 2: ctypes replay of the model.m forward sequence
# ---------------------------------------------------------------------------

EMU_DRIVER = textwrap.dedent("""
    import ctypes, sys
    import numpy as np

    lib = ctypes.CDLL(sys.argv[1])
    lib.MXGetLastError.restype = ctypes.c_char_p

    def check(rc):
        assert rc == 0, lib.MXGetLastError().decode()

    # argv[2]: "-symbol.json path" or "-" (artifact mode, like
    # model.load_artifact); argv[3]: .params or .mxa bytes
    symbol = b"" if sys.argv[2] == "-" else open(sys.argv[2], "rb").read()
    params = open(sys.argv[3], "rb").read()

    # MATLAB-side input: x is (H, W, C, N) col-major with H != W
    H, W, C, N = 6, 8, 1, 4
    rng = np.random.RandomState(7)
    x = np.asfortranarray(rng.randn(H, W, C, N).astype(np.float32))

    # model.m to_c_order: permute([2 1 3 4]) then flatten col-major
    flat = np.transpose(x, (1, 0, 2, 3)).flatten(order="F")
    # model.m cshape: reverse of the permuted size -> (N, C, H, W)
    cshape = (ctypes.c_uint32 * 4)(N, C, H, W)
    # sanity of the layout contract itself: this must be the row-major
    # NCHW tensor the runtime expects
    assert np.array_equal(flat, np.transpose(x, (3, 2, 0, 1)).ravel())

    handle = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 4)
    check(lib.MXPredCreatePartialOut(
        ctypes.c_char_p(symbol), params, len(params), 1, 0,
        1, keys, indptr, cshape, 0, None, ctypes.byref(handle)))

    buf = flat.astype(np.float32)
    check(lib.MXPredSetInput(handle, b"data",
                             buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                             buf.size))
    check(lib.MXPredForward(handle))

    pshape = ctypes.POINTER(ctypes.c_uint32)()
    pdim = ctypes.c_uint32()
    check(lib.MXPredGetOutputShape(handle, 0, ctypes.byref(pshape),
                                   ctypes.byref(pdim)))
    out_cshape = [pshape[i] for i in range(pdim.value)]
    out = np.zeros(int(np.prod(out_cshape)), np.float32)
    check(lib.MXPredGetOutput(handle, 0,
                              out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                              out.size))
    check(lib.MXPredFree(handle))

    # model.m fetch_output: reshape(reverse shape) col-major
    msiz = out_cshape[::-1]
    out_matlab = out.reshape(msiz, order="F")

    np.save(sys.argv[4], out_matlab)
    np.save(sys.argv[4] + "_nchw.npy",
            np.transpose(x, (3, 2, 0, 1)).copy())
    print("EMU_OK", out_cshape)
""")


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_matlab_call_sequence_matches_python(tmp_path):
    import mxnet_tpu as mx

    r = subprocess.run(["make", "c_predict"], cwd=SRC,
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.skip("predict shim build failed: %s" % r.stderr[-500:])
    lib = os.path.join(SRC, "build", "libmxtpu_predict.so")

    # conv net with H != W so a layout swap cannot cancel out
    H, W, N = 6, 8, 4
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=3, name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=5, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.randn(16, 1, H, W).astype(np.float32)
    y = rng.randint(0, 5, size=(16,)).astype(np.float32)
    mod = mx.mod.Module(net)
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=N), num_epoch=1,
            initializer=mx.init.Xavier())
    prefix = str(tmp_path / "net")
    mod.save_checkpoint(prefix, 1)

    driver = tmp_path / "emu.py"
    driver.write_text(EMU_DRIVER)
    out_npy = str(tmp_path / "out.npy")
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(driver), lib,
                        prefix + "-symbol.json", prefix + "-0001.params",
                        out_npy],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "EMU_OK" in r.stdout

    out_matlab = np.load(out_npy)          # (K, N) — MATLAB column scores
    x_nchw = np.load(out_npy + "_nchw.npy")

    expected = mod.predict(
        mx.io.NDArrayIter(x_nchw, np.zeros(N, np.float32),
                          batch_size=N)).asnumpy()  # (N, K)

    assert out_matlab.shape == (5, N)
    np.testing.assert_allclose(out_matlab, expected.T, rtol=1e-4, atol=1e-5)

    # artifact mode (model.load_artifact): same call sequence against the
    # Python-free native runtime — PartialOut with 0 outputs must bind.
    # That runtime needs a PJRT plugin (its default is libtpu).
    if not os.environ.get("MXTPU_PJRT_PLUGIN"):
        pytest.skip("the shim half passed; artifact mode needs a PJRT "
                    "plugin (set MXTPU_PJRT_PLUGIN)")
    r = subprocess.run(["make", "c_predict_native"], cwd=SRC,
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.skip("native predict build failed: %s" % r.stderr[-500:])
    native = os.path.join(SRC, "build", "libmxtpu_predict_native.so")

    mxa = str(tmp_path / "net.mxa")
    arg_p, aux_p = mod.get_params()
    mx.export_predict_artifact(net, arg_p, aux_p, {"data": (N, 1, H, W)},
                               mxa, platform="cpu")
    out2_npy = str(tmp_path / "out2.npy")
    r = subprocess.run([sys.executable, str(driver), native, "-", mxa,
                        out2_npy],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    out_artifact = np.load(out2_npy)
    np.testing.assert_allclose(out_artifact, expected.T, rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Tier 3: interpreter-gated
# ---------------------------------------------------------------------------

@pytest.mark.skipif(shutil.which("octave") is None, reason="no octave")
def test_parse_json_under_octave():
    r = subprocess.run(
        ["octave", "--no-gui", "-q", os.path.join(PKG, "tests",
                                                  "test_parse_json.m")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "PARSE_JSON_OK" in r.stdout


@pytest.mark.skipif(shutil.which("matlab") is None, reason="no matlab")
def test_prediction_under_matlab(tmp_path):
    import mxnet_tpu as mx

    # fixtures for matlab/tests/test_prediction.m
    H, W, N = 6, 8, 4
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=2, name="conv1")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(1)
    X = rng.randn(8, 1, H, W).astype(np.float32)
    y = rng.randint(0, 3, size=(8,)).astype(np.float32)
    mod = mx.mod.Module(net)
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=N), num_epoch=1,
            initializer=mx.init.Xavier())
    mod.save_checkpoint(str(tmp_path / "net"), 1)

    x_m = np.asfortranarray(
        rng.randn(H, W, 1, N).astype(np.float32))          # MATLAB layout
    x_nchw = np.transpose(x_m, (3, 2, 0, 1)).copy()
    expected = mod.predict(
        mx.io.NDArrayIter(x_nchw, np.zeros(N, np.float32),
                          batch_size=N)).asnumpy().T

    np.savetxt(tmp_path / "input.csv", x_m.flatten(order="F"))
    np.savetxt(tmp_path / "insize.csv", np.array([H, W, 1, N]))
    np.savetxt(tmp_path / "expected.csv", expected.flatten(order="F"))

    env = dict(os.environ)
    env["MXNETTPU_FIXDIR"] = str(tmp_path)
    r = subprocess.run(
        ["matlab", "-batch",
         "run('%s')" % os.path.join(PKG, "tests", "test_prediction.m")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "PREDICTION_OK" in r.stdout
