"""Python-free deployment artifacts (the amalgamation analog).

Reference: ``amalgamation/README.md:1-13`` + ``src/c_api/c_predict_api.cc:1``
— the reference's predict stack exists so models run where the framework
does not (single-file build, JNI/mobile targets). The TPU-native equivalent
is an ahead-of-time *export*: the bound graph is lowered to StableHLO with
``jax.export`` and written into a single ``.mxa`` container together with
the parameters (reference ``.params`` wire format) and a JSON manifest.
``libmxtpu_predict_native.so`` (src/c_predict_pjrt.cc) then loads the
artifact through any PJRT plugin (``libtpu.so`` on TPU hosts) with **no
Python anywhere in the process** — the deployment substrate the reference's
amalgamation provided.

Container layout (little-endian)::

    8 bytes   magic "MXTPUAR1"
    u64 n     manifest length   | n bytes of JSON (see below)
    u64 n     program length    | n bytes of StableHLO portable bytecode
    u64 n     params length     | n bytes of NDArray-dict save format
                                  (magic 0x112; keys "arg:NAME"/"aux:NAME")

The exported StableHLO function's flat argument order is
``inputs... , args..., auxs...`` exactly as listed in the manifest; outputs
follow ``symbol.list_outputs()``.
"""
from __future__ import annotations

import io
import json
import struct

import numpy as np

from . import compileobs as _compileobs
from . import ndarray as nd
from .base import MXNetError
from .executor import build_graph_fn

MAGIC = b"MXTPUAR1"

__all__ = ["export_predict_artifact", "export_train_artifact",
           "load_artifact_manifest", "MAGIC"]


def _shape_of(x):
    return tuple(int(d) for d in x.shape)


def export_predict_artifact(symbol, arg_params, aux_params, input_shapes,
                            path, platform="tpu", dtype="float32",
                            matmul_precision="highest"):
    """AOT-export ``symbol``'s inference forward into a ``.mxa`` file.

    Parameters
    ----------
    symbol : Symbol
        The network. Outputs follow ``symbol.list_outputs()``.
    arg_params, aux_params : dict[str, NDArray | np.ndarray]
        Trained parameters (``Module.get_params()`` /
        ``model.load_checkpoint`` shapes).
    input_shapes : dict[str, tuple]
        Shapes for the data inputs (e.g. ``{"data": (1, 3, 224, 224)}``).
        Label inputs of loss heads are auto-inferred and marked
        ``"kind": "label"`` in the manifest; the native runtime feeds them
        zeros unless the client sets them.
    path : str
        Output file. Convention: ``model.mxa``.
    platform : str
        Lowering platform for ``jax.export`` (``"tpu"`` or ``"cpu"``). The
        plain conv/matmul StableHLO this framework emits is
        platform-neutral; the tag only gates jax's own runtime check.
    matmul_precision : str
        jax matmul precision baked into the module. ``"highest"`` keeps
        fp32 accuracy on the MXU (3-pass bf16) so native outputs match the
        Python executor tightly; use ``"default"`` for speed.
    """
    import jax

    graph_fn, arg_names, aux_names = build_graph_fn(symbol)

    arg_params = {k: (v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v))
                  for k, v in (arg_params or {}).items()}
    aux_params = {k: (v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v))
                  for k, v in (aux_params or {}).items()}

    input_names = [n for n in arg_names if n not in arg_params]
    param_names = [n for n in arg_names if n in arg_params]
    missing_aux = [n for n in aux_names if n not in aux_params]
    if missing_aux:
        raise MXNetError("missing aux params: %s" % missing_aux)

    # resolve shapes: caller gives data shapes, label heads are inferred
    # (reference MXPredCreate also takes only data shapes)
    shapes = {n: tuple(s) for n, s in input_shapes.items()}
    unknown = [n for n in input_names if n not in shapes]
    kinds = {n: "data" for n in shapes}
    # only label-named inputs may be auto-inferred and zero-fed (reference
    # convention: loss heads call theirs <name>_label / `label`). Anything
    # else without a shape is almost certainly a parameter missing from
    # arg_params — exporting it as a zero input would be silently wrong.
    inferable = [n for n in unknown
                 if n == "label" or n.endswith("_label")]
    not_label = [n for n in unknown if n not in inferable]
    if not_label:
        raise MXNetError(
            "arguments %s have neither a value in arg_params nor a shape in "
            "input_shapes; if they are network inputs pass their shapes, if "
            "they are parameters add them to arg_params" % not_label)
    if inferable:
        inferred, _, _ = symbol.infer_shape_partial(**shapes)
        for n, shp in zip(arg_names, inferred):
            if n in inferable and shp is not None and 0 not in tuple(shp):
                shapes[n] = tuple(shp)
                kinds[n] = "label"
        unknown = [n for n in input_names if n not in shapes]
        if unknown:
            raise MXNetError("cannot infer shapes for inputs %s" % unknown)
    bad = [n for n in input_shapes if n not in input_names]
    if bad:
        raise MXNetError("input_shapes for non-input names %s (bound params?)"
                         % bad)

    np_dtype = np.dtype(dtype)
    n_in, n_arg = len(input_names), len(param_names)

    def fwd(*flat):
        inputs = dict(zip(input_names, flat[:n_in]))
        params = dict(zip(param_names, flat[n_in:n_in + n_arg]))
        auxs = list(flat[n_in + n_arg:])
        arg_list = [inputs[n] if n in inputs else params[n]
                    for n in arg_names]
        outs, _ = graph_fn(arg_list, auxs, None, False)
        return tuple(outs)

    in_specs = ([jax.ShapeDtypeStruct(shapes[n], np_dtype)
                 for n in input_names]
                + [jax.ShapeDtypeStruct(_shape_of(arg_params[n]),
                                        arg_params[n].dtype)
                   for n in param_names]
                + [jax.ShapeDtypeStruct(_shape_of(aux_params[n]),
                                        aux_params[n].dtype)
                   for n in aux_names])

    with jax.default_matmul_precision(matmul_precision), \
            _compileobs.record_compile(
                "export.predict",
                site="mxnet_tpu/export_artifact.py:export_predict_artifact"):
        # fwlint: disable=untracked-jit — the lowering wall is charged via the record_compile scope above
        exported = jax.export.export(
            _compileobs.raw_jit(
                fwd, "export.predict",
                site="mxnet_tpu/export_artifact.py:export_predict_artifact"),
            platforms=[platform])(*in_specs)
    # Re-serialize the StableHLO at the MAXIMUM backward-compatibility
    # target (oldest VHLO version) instead of jax.export's 12-week window:
    # a deployment artifact must load into whatever PJRT plugin the serving
    # host ships, and plugins can lag the StableHLO producer by far more
    # than 12 weeks.
    program = _serialize_max_compat(exported)

    # jax.export dead-code-eliminates unused module arguments (e.g. a
    # fix_gamma BatchNorm's gamma, an inference-ignored label): the
    # executable takes only module_kept_var_idx. The manifest records the
    # kept flag so the native runtime passes exactly the surviving args.
    kept = set(exported.module_kept_var_idx)
    flat_names = (input_names
                  + ["arg:" + n for n in param_names]
                  + ["aux:" + n for n in aux_names])
    kept_params = [n for i, n in enumerate(flat_names)
                   if i in kept and i >= n_in]

    out_names = symbol.list_outputs()
    out_avals = exported.out_avals
    manifest = {
        "version": 1,
        "platform": platform,
        "matmul_precision": matmul_precision,
        "inputs": [{"name": n, "shape": list(shapes[n]),
                    "dtype": str(np_dtype), "kind": kinds.get(n, "data"),
                    "kept": input_names.index(n) in kept}
                   for n in input_names],
        "params": kept_params,
        "outputs": [{"name": n, "shape": [int(d) for d in a.shape],
                     "dtype": str(np.dtype(a.dtype))}
                    for n, a in zip(out_names, out_avals)],
    }

    blob = io.BytesIO()
    params_dict = {}
    for key in kept_params:  # DCE'd params stay out of the artifact too
        kind, _, n = key.partition(":")
        src = arg_params if kind == "arg" else aux_params
        params_dict[key] = nd.array(np.asarray(src[n]))
    _save_params_to(blob, params_dict)

    mjs = json.dumps(manifest, indent=1).encode()
    pbytes = blob.getvalue()
    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<Q", len(mjs)))
        f.write(mjs)
        f.write(struct.pack("<Q", len(program)))
        f.write(program)
        f.write(struct.pack("<Q", len(pbytes)))
        f.write(pbytes)
    return manifest


def export_train_artifact(symbol, input_shapes, path, optimizer="sgd",
                          optimizer_params=None, initializer=None,
                          arg_params=None, aux_params=None, platform="tpu",
                          matmul_precision="highest", seed=0,
                          compute_dtype=None, num_devices=1):
    """AOT-export a full TRAINING step into a ``.mxa`` file (kind="train").

    Goes beyond the reference's deployment stack: its amalgamation/predict
    API was inference-only (``c_predict_api.h``) — here the whole fused
    step (forward + backward + optimizer update, the same trace
    ``Module.fit`` runs on the fused path) is lowered with ``jax.export``
    so a C client can TRAIN through the PJRT C API with no Python in the
    process. See ``src/c_predict_pjrt.cc`` (MXTrainNative*) for the native
    runtime and ``docs/deployment.md`` for the workflow.

    The exported function's flat signature (role-tagged in the manifest)::

        step(params..., states..., auxs..., inputs..., lr, t)
          -> (new_params..., new_states..., new_auxs..., outputs...)

    ``lr`` is an f32 scalar the client controls per step (scheduling stays
    host-side, like the classic path); ``t`` is the 1-based update counter
    (Adam bias correction etc.); param/state/aux buffers are donated, so a
    PJRT runtime carries them in place between steps. Initial params come
    from ``arg_params``/``aux_params`` or ``initializer`` (default Xavier),
    and ship in the artifact's params section (keys ``arg:``/``aux:``/
    ``state:<name>:<slot>``) together with the loss-output flags the client
    can use for readout.

    Stochastic graphs (Dropout etc.) derive their per-step rng key inside
    the program from ``t`` and the baked ``seed`` — deterministic replay,
    nothing extra for the C client to feed.

    ``compute_dtype="bfloat16"`` bakes the TPU-native mixed-precision
    recipe into the artifact (same as the fused fit path: fp32 master
    params and optimizer slots at the boundary, bf16 graph compute, fp32
    gradients through the cast); the flat C signature stays float32.

    ``num_devices=N`` exports a data-parallel SPMD step: params/optimizer
    state replicate, data/label shard on the batch axis (N must divide the
    batch), and XLA's GSPMD partitioner inserts the gradient all-reduce —
    the math is identical to the single-device step. The manifest carries
    per-arg sharding tags plus the serialized compile options
    (num_partitions=N), and the native runtime executes across N
    addressable PJRT devices from the one file. Export needs N visible
    devices of ``platform`` (on a pod host they are the chips; in CI,
    XLA_FLAGS=--xla_force_host_platform_device_count virtualizes CPUs).
    """
    import jax
    import jax.numpy as jnp

    from . import initializer as init_mod
    from .parallel import build_mesh
    from .parallel.spmd import SPMDTrainer

    # label-head shape inference, same contract as the predict export
    shapes = {n: tuple(s) for n, s in input_shapes.items()}
    arg_names = symbol.list_arguments()
    known = set(shapes) | set(arg_params or {})
    unknown = [n for n in arg_names if n not in known]
    label_like = [n for n in unknown if n == "label" or n.endswith("_label")]
    if label_like:
        inferred, _, _ = symbol.infer_shape_partial(**shapes)
        for n, shp in zip(arg_names, inferred):
            if n in label_like and shp is not None and 0 not in tuple(shp):
                shapes[n] = tuple(shp)

    data_shapes = [(n, s) for n, s in shapes.items() if n not in label_like]
    label_shapes = [(n, shapes[n]) for n in label_like if n in shapes]

    # SPMD preconditions are validated BEFORE any initializer runs: a
    # failed export must not consume RNG draws (it would silently change
    # the next export's initial weights in the same process)
    if num_devices > 1:
        for n, _ in data_shapes + label_shapes:
            shp = shapes[n]
            if not shp or shp[0] % num_devices != 0:
                raise ValueError(
                    "num_devices=%d must divide input '%s' batch dim %r"
                    % (num_devices, n, shp[:1]))
        try:
            n_vis = len(jax.devices(platform))
        except RuntimeError as e:  # backend absent: surface the same
            raise ValueError(                 # documented ValueError
                "export with num_devices=%d needs %d visible %s devices "
                "(no %s backend: %s)"
                % (num_devices, num_devices, platform, platform, e)) from e
        if n_vis < num_devices:
            raise ValueError(
                "export with num_devices=%d needs %d visible %s devices "
                "(found %d); on CPU set "
                "XLA_FLAGS=--xla_force_host_platform_device_count"
                % (num_devices, num_devices, platform, n_vis))

    mesh = build_mesh({"dp": 1}, list(jax.devices("cpu"))[:1])
    trainer = SPMDTrainer(symbol, mesh, data_shapes=data_shapes,
                          label_shapes=label_shapes, optimizer=optimizer,
                          optimizer_params=optimizer_params, donate=False,
                          compute_dtype=compute_dtype)

    # ---- initial values (host-side numpy; nothing touches a device) ------
    from . import ndarray as nd

    if initializer is None:
        initializer = init_mod.Xavier()
    arg_params = dict(arg_params or {})
    aux_params = dict(aux_params or {})
    params0, states0, auxs0 = {}, {}, {}
    for n in trainer.param_names:
        if n in arg_params:
            v = arg_params[n]
            v = v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)
        else:
            host = nd.zeros(trainer.arg_shapes[n])
            initializer(n, host)
            v = host.asnumpy()
        params0[n] = v.astype(np.float32)
        states0[n] = trainer.rule.init_state(trainer.arg_shapes[n],
                                             np.float32)
    for n in trainer.aux_names:
        if n in aux_params:
            v = aux_params[n]
            v = v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)
        else:
            host = nd.zeros(trainer.aux_shapes[n])
            initializer(n, host)
            v = host.asnumpy()
        auxs0[n] = v.astype(np.float32)

    # ---- the flat step ---------------------------------------------------
    rule = trainer.rule
    base_wd = trainer.optimizer.wd
    from .parallel import fused_opt as _fo

    lr_mult, wd_mult = _fo.mults_for(trainer.optimizer, trainer.param_names)
    pnames, anames = trainer.param_names, trainer.aux_names
    nslot = rule.nslot
    stochastic = trainer._stochastic

    def flat_step(*flat):
        i = 0
        params = {n: flat[i + k] for k, n in enumerate(pnames)}
        i += len(pnames)
        states = {}
        for n in pnames:
            states[n] = tuple(flat[i:i + nslot])
            i += nslot
        auxs = {n: flat[i + k] for k, n in enumerate(anames)}
        i += len(anames)
        inputs = {}
        for n, _ in data_shapes + label_shapes:
            inputs[n] = flat[i]
            i += 1
        lr, t = flat[i], flat[i + 1]
        rng = jax.random.PRNGKey(jnp.uint32(seed) + t.astype(jnp.uint32)) \
            if stochastic else None
        grads, new_auxs, outs = trainer._make_grads(params, auxs, inputs, rng)
        out_flat = []
        new_states = []
        for n in pnames:
            p, s = rule.apply(params[n], grads[n], states[n],
                              lr * lr_mult[n], base_wd * wd_mult[n], t)
            out_flat.append(p)
            new_states.extend(s)
        out_flat.extend(new_states)
        out_flat.extend(new_auxs[n] for n in anames)
        # graph outputs keep the C contract at float32 even under a bf16
        # compute_dtype (the native GetOutput surface is f32-only)
        out_flat.extend(
            o.astype(np.float32) if jnp.issubdtype(o.dtype, jnp.floating)
            and o.dtype != np.float32 else o
            for o in outs)
        return tuple(out_flat)

    n_params, n_auxs = len(pnames), len(anames)
    n_states = n_params * nslot
    n_inputs = len(data_shapes) + len(label_shapes)
    donate = tuple(range(n_params + n_states + n_auxs))

    f32 = np.dtype(np.float32)
    in_specs = (
        [jax.ShapeDtypeStruct(trainer.arg_shapes[n], f32) for n in pnames]
        + [jax.ShapeDtypeStruct(trainer.arg_shapes[n], f32)
           for n in pnames for _ in range(nslot)]
        + [jax.ShapeDtypeStruct(trainer.aux_shapes[n], f32) for n in anames]
        + [jax.ShapeDtypeStruct(shapes[n], f32) for n, _ in data_shapes]
        + [jax.ShapeDtypeStruct(shapes[n], f32) for n, _ in label_shapes]
        + [jax.ShapeDtypeStruct((), f32), jax.ShapeDtypeStruct((), np.int32)]
    )

    # ---- SPMD shardings (num_devices > 1): dp over the batch axis --------
    compile_options_b64 = None
    in_shard_tags = ["rep"] * len(in_specs)
    out_shard_tags = None
    jit_kwargs = dict(donate_argnums=donate)
    if num_devices > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devs = list(jax.devices(platform))  # presence validated up front
        emesh = Mesh(np.array(devs[:num_devices]), ("dp",))
        rep = NamedSharding(emesh, PartitionSpec())
        batched = NamedSharding(emesh, PartitionSpec("dp"))
        n_fixed = n_params + n_states + n_auxs
        in_shardings = [rep] * n_fixed
        for k in range(len(data_shapes) + len(label_shapes)):
            in_shard_tags[n_fixed + k] = "batch"
            in_shardings.append(batched)
        in_shardings += [rep, rep]  # lr, t
        out_avals_probe = jax.eval_shape(flat_step, *in_specs)
        # only outputs whose leading dim IS the global batch shard; a
        # divisibility-only test would mis-tag hidden-dim outputs and buy a
        # pointless per-step reshard
        global_batch = shapes[data_shapes[0][0]][0] if data_shapes else -1
        out_shardings, out_shard_tags = [], []
        for k, o in enumerate(out_avals_probe):
            if (k >= n_fixed and len(o.shape)
                    and o.shape[0] == global_batch):
                out_shardings.append(batched)
                out_shard_tags.append("batch")
            else:
                out_shardings.append(rep)
                out_shard_tags.append("rep")
        jit_kwargs.update(in_shardings=tuple(in_shardings),
                          out_shardings=tuple(out_shardings))
        compile_options_b64 = _spmd_compile_options_b64(num_devices)

    with jax.default_matmul_precision(matmul_precision), \
            _compileobs.record_compile(
                "export.train_step",
                site="mxnet_tpu/export_artifact.py:export_train_artifact"):
        # fwlint: disable=untracked-jit — the lowering wall is charged via the record_compile scope above
        exported = jax.export.export(
            _compileobs.raw_jit(
                flat_step, "export.train_step",
                site="mxnet_tpu/export_artifact.py:export_train_artifact",
                **jit_kwargs),
            platforms=[platform])(*in_specs)
    program = _serialize_max_compat(exported)
    kept = set(exported.module_kept_var_idx)

    # ---- manifest --------------------------------------------------------
    args_desc = []

    def arg_row(name, role, shape, idx):
        args_desc.append({
            "name": name, "role": role, "shape": [int(d) for d in shape],
            "dtype": "int32" if role == "t" else "float32",
            "kept": idx in kept, "donated": idx in set(donate),
            "sharding": in_shard_tags[idx]})

    idx = 0
    for n in pnames:
        arg_row(n, "param", trainer.arg_shapes[n], idx); idx += 1
    for n in pnames:
        for k in range(nslot):
            arg_row("%s:%d" % (n, k), "state", trainer.arg_shapes[n], idx)
            idx += 1
    for n in anames:
        arg_row(n, "aux", trainer.aux_shapes[n], idx); idx += 1
    for n, _ in data_shapes:
        arg_row(n, "data", shapes[n], idx); idx += 1
    for n, _ in label_shapes:
        arg_row(n, "label", shapes[n], idx); idx += 1
    arg_row("lr", "lr", (), idx); idx += 1
    arg_row("t", "t", (), idx); idx += 1

    out_names = symbol.list_outputs()
    outs_desc = (
        [{"name": n, "role": "param"} for n in pnames]
        + [{"name": "%s:%d" % (n, k), "role": "state"}
           for n in pnames for k in range(nslot)]
        + [{"name": n, "role": "aux"} for n in anames]
        + [{"name": n, "role": "out"} for n in out_names])
    for k, (d, a) in enumerate(zip(outs_desc, exported.out_avals)):
        d["shape"] = [int(x) for x in a.shape]
        d["dtype"] = str(np.dtype(a.dtype))
        d["sharding"] = out_shard_tags[k] if out_shard_tags else "rep"

    manifest = {
        "version": 2,
        "kind": "train",
        "num_devices": int(num_devices),
        "platform": platform,
        "matmul_precision": matmul_precision,
        "compute_dtype": str(np.dtype(compute_dtype))
        if compute_dtype is not None else "float32",
        "optimizer": type(trainer.optimizer).__name__,
        "nslot": nslot,
        "t0": 1,
        "seed": int(seed),
        "loss_outputs": [bool(f) for f in trainer._loss_flags],
        "args": args_desc,
        "outputs": outs_desc,
    }
    if compile_options_b64 is not None:
        manifest["compile_options"] = compile_options_b64

    blob = io.BytesIO()
    params_dict = {}
    for n in pnames:
        params_dict["arg:" + n] = nd.array(params0[n])
        for k in range(nslot):
            params_dict["state:%s:%d" % (n, k)] = nd.array(
                np.asarray(states0[n][k], np.float32))
    for n in anames:
        params_dict["aux:" + n] = nd.array(auxs0[n])
    _save_params_to(blob, params_dict)

    mjs = json.dumps(manifest, indent=1).encode()
    pbytes = blob.getvalue()
    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<Q", len(mjs)))
        f.write(mjs)
        f.write(struct.pack("<Q", len(program)))
        f.write(program)
        f.write(struct.pack("<Q", len(pbytes)))
        f.write(pbytes)
    return manifest


def _spmd_compile_options_b64(num_devices):
    """Serialized xla.CompileOptionsProto for 1 replica x N partitions with
    SPMD partitioning — the native runtime compiles the exported program
    with exactly these options (see compile_options_blob in
    src/c_predict_pjrt.cc for the single-device default it replaces)."""
    import base64

    from jax._src import compiler as _jax_compiler

    opts = _jax_compiler.get_compile_options(
        num_replicas=1, num_partitions=num_devices,
        device_assignment=np.arange(num_devices).reshape(1, num_devices),
        use_spmd_partitioning=True)
    return base64.b64encode(opts.SerializeAsString()).decode()


def _serialize_max_compat(exported):
    """Downgrade the exported module's VHLO serialization to the oldest
    compatible version. Falls back to jax.export's own serialization if the
    version-targeting API is unavailable."""
    try:
        import jaxlib.mlir.dialects.stablehlo as hlo
        from jax._src.lib import xla_client
        target = hlo.get_version_from_compatibility_requirement(
            hlo.StablehloCompatibilityRequirement.MAX)
        return xla_client._xla.mlir.serialize_portable_artifact(
            exported.mlir_module(), target, False)
    except Exception:
        return exported.mlir_module_serialized


def _save_params_to(fileobj, params_dict):
    """nd.save writes to a path; route it through a temp file into a stream
    (the save format is the interchange contract, so reuse it exactly)."""
    import os
    import tempfile
    fd, tmp = tempfile.mkstemp(suffix=".params")
    os.close(fd)
    try:
        nd.save(tmp, params_dict)
        with open(tmp, "rb") as f:
            fileobj.write(f.read())
    finally:
        os.unlink(tmp)


def load_artifact_manifest(path):
    """Read back the manifest (and section sizes) of a ``.mxa`` file —
    the Python-side mirror of the native loader, used by tests to assert
    both sides parse the same container."""
    with open(path, "rb") as f:
        if f.read(8) != MAGIC:
            raise MXNetError("not an .mxa artifact: %s" % path)
        (mlen,) = struct.unpack("<Q", f.read(8))
        manifest = json.loads(f.read(mlen).decode())
        (plen,) = struct.unpack("<Q", f.read(8))
        f.seek(plen, 1)
        (qlen,) = struct.unpack("<Q", f.read(8))
        return manifest, plen, qlen
