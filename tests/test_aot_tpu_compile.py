"""What the chip needs, checked without the chip.

* The Pallas kernels of the main path compile for a DESCRIBED TPU v5e (the
  TPU compiler is installed; no device is attached) at the serving smoke's
  widths and at head_dim 128 — interpret mode cannot see what Mosaic
  refuses. Also through the public entry points: a TPU lowering made from
  this CPU-default process must hold the kernel (``tpu_custom_call``), not
  the reference lowering.
* The serving programs leave the KV pool where it lies: over a pool from
  ``PageSpec.lane_dense`` the compiled ``decode`` and ``prefill`` hold no
  copy and no slice the size of the pool or of a layer of it.
* ``chip_smoke.py`` fails fast and says so when jax has no TPU.
* The compile-cache directory is decided by the environment, then by one
  fixed path — never by the process.
* ``mx.tpu(i)`` names a chip or raises; host devices stand in only under the
  test rig's CPU pin.

The file name sorts first on purpose: tier-1 runs against a clock.
"""
import functools
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import attention as A

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- kernels
@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip as a sharding. jax's persistent cache stays
    off meanwhile: it would store these compiles, cannot read them back
    without a chip, and warns about that on every later run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip("cannot describe a v5e topology: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _flash_shapes(chip, d):
    return [jax.ShapeDtypeStruct((2, 12, 1024, d), jnp.bfloat16,
                                 sharding=chip)] * 3


def _paged_shapes(chip, h, d, dtype, lanes=None, rows=None, layers=None,
                  blocks=(2049, 16)):
    """Serving shapes: B=32 streams, 64 table slots, ``blocks`` = (pool
    blocks, block size); page rows ``(h, d)`` unless given, the whole pool
    if ``layers``."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    q = (32, h, d) if lanes is None else (32, lanes, h, d)
    ctx = (32,) if lanes is None else (32, lanes)
    pages = s(((layers,) if layers else ()) + blocks + (rows or (h, d)),
              dtype)
    return (s(q, dtype), pages, pages, s((32, 64), jnp.int32),
            s(ctx, jnp.int32))


@pytest.mark.parametrize("d", [64, 128])
def test_flash_forward_compiles_for_v5e(v5e, d):
    text = _compiled_text(
        functools.partial(A._pallas_forward, causal=True, sm_scale=0.125),
        *_flash_shapes(v5e, d))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [64, 128])
def test_flash_backward_compiles_for_v5e(v5e, d):
    q, k, v = _flash_shapes(v5e, d)
    lse = jax.ShapeDtypeStruct(q.shape[:3], jnp.float32, sharding=v5e)
    text = _compiled_text(
        functools.partial(A._pallas_backward, causal=True, sm_scale=0.125),
        q, k, v, q, lse, q)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lanes", [None, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("h,d,rows,layers,blocks", [
    (12, 64, None, None, (2049, 16)), (16, 128, None, None, (2049, 16)),
    (16, 64, (8, 128), None, (2049, 16)), (16, 64, (8, 128), 2, (2049, 16)),
    (16, 64, (8, 128), 24, (513, 16)), (16, 128, None, 8, (1025, 64))],
    ids=["12x64", "16x128", "16x64-packed", "16x64-packed-pool",
         "gpt2-medium-pool", "olmoe-pool"])
def test_paged_kernels_compile_for_v5e(v5e, h, d, rows, layers, blocks, dtype,
                                       lanes):
    """The last two are the benchmark's pools (fp32 and bf16 there)."""
    kernel = A._paged_pallas if lanes is None else A._paged_pallas_multi
    text = _compiled_text(
        functools.partial(kernel, sm_scale=0.125,
                          layer=1 if layers else None),
        *_paged_shapes(v5e, h, d, dtype, lanes, rows, layers, blocks))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [None, 512], ids=["full", "w512"])
@pytest.mark.parametrize("lanes", [4, 1, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_head_major_paged_kernel_compiles_for_v5e(v5e, dtype, lanes, window):
    """Phi-4-mini-flash's pages, ten rows of 128 lanes as ``(G, bs, W)``
    blocks of 64: the block's two batched matmuls (bf16 operands as they
    are, float32 at full precision) and the softmax over ``(G, Tp, bs)``
    between them, T a tile's worth or not."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    pages = s((8, 641, 10, 64, 128), dtype)
    text = _compiled_text(
        functools.partial(A._paged_pallas_multi, sm_scale=0.125, layer=1,
                          window=window, head_major=True),
        s((64, lanes, 10, 128), dtype), pages, pages,
        s((64, 40), jnp.int32), s((64, lanes), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page,dtype,c", [
    ((16, 8, 128), jnp.float32, 8),      # GPT-2 medium: 64 KB pages
    ((64, 16, 128), jnp.bfloat16, 2),    # OLMoE: 256 KB pages
    ((16, 12, 64), jnp.float32, 4),      # unpacked rows, padded to (16, 128)
    ((16, 8, 128), jnp.bfloat16, 8),     # half a bf16 tile of rows
    ((8, 3, 32), jnp.float32, 16)],      # small rows fill whole tiles too
    ids=["gpt2-medium", "olmoe", "12x64", "8x128-bf16", "tiny"])
def test_blocks_per_fetch_follow_the_page(page, dtype, c):
    """c is read off the page: a fetch of 128 KB or more, a scratch (two
    slots of K and of V) under 8 MB, never more blocks than the table."""
    bs, g, w = page
    assert A._paged_blocks_per_fetch(bs, g, w, dtype, 64) == c
    assert A._paged_blocks_per_fetch(bs, g, w, dtype, 3) == min(c, 3)
    in_vmem = A._paged_page_bytes(bs, g, w, dtype)    # rows padded to tiles
    assert 4 * c * in_vmem <= 8 << 20
    assert c * in_vmem >= 128 << 10
    if w >= 64:                                       # also as the rows count
        assert c * bs * g * w * jnp.dtype(dtype).itemsize >= 128 << 10


# --------------------------------------------------- the pool stays put
def _serving_program(chip, name, page, arch="gpt2"):
    """``model.decode`` (B=32), ``model.decode_chunk`` (the loop the engine
    dispatches: four rows of results) or ``model.prefill`` (S=512) at the
    benchmark's widths cut to 2 layers, pool donated, compiled for the
    chip: GPT-2 medium in fp32 over blocks of 16, or OLMoE-1B-7B in bf16
    over blocks of 64. Returns (compiled, pool shape, bytes an element)."""
    from mxnet_tpu.serving import model as M

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    if arch == "gpt2":
        cfg, dtype, bs, blocks = (
            M.ModelConfig(50257, 2, 1024, 16, 4096, 1024), jnp.float32, 16,
            513)
    elif arch == "ouro":
        # Ouro-2.6B's widths and four passes over blocks of 32: a pool of
        # four parts of 145 blocks, one pass's two layer bodies a program
        cfg, dtype, bs, blocks = (
            M.ModelConfig(49152, 2, 2048, 16, 5632, 4096, norm="rms",
                          pos="rope", rope_theta=1e6, head_dim=128,
                          bias=False, ffn_gated=True, norm_eps=1e-6,
                          loop_steps=4, post_norm=True),
            jnp.bfloat16, 32, 4 * 145)
    else:
        cfg, dtype, bs, blocks = (
            M.ModelConfig(50304, 2, 2048, 16, 1024, 4096, norm="rms",
                          pos="rope", qk_norm=True, head_dim=128,
                          num_experts=64, experts_per_tok=8, bias=False),
            # the chunk over the benchmark's own 1,025 blocks: a pool of
            # 129 is small enough for the compiler to stage a layer of it
            # through fast memory inside the loop, which says nothing of
            # the real one
            jnp.bfloat16, 64, 1025 if name == "chunk" else 129)
    params = {k: s(v, dtype) for k, v in M.param_shapes(cfg).items()}
    pool = s((cfg.num_layers, blocks, bs) + page, dtype)
    if name == "decode":
        fn, donate = M.decode, (5, 6)
        args = (s((32,)), s((32,)), s((32, cfg.max_len // bs)), s((32,)))
    elif name == "chunk":
        fn, donate = functools.partial(M.decode_chunk, chunk=4), (8, 9)
        args = (s((32,)), s((32,)), s((32, cfg.max_len // bs)), s((32,)),
                s((32,)), s((32,)), s(()))
    else:
        # the program the engine runs: a pack's [starts, lengths] for the
        # model with experts, one prompt's bare length for the two without
        fn, donate = M.prefill, (4, 5)
        width = M.pack_width(cfg, 512)
        args = (s((1, 512)), s((2, width) if width > 1 else ()),
                s((M.pack_blocks(cfg, 512, bs),)))
    jitted = jax.jit(functools.partial(fn, cfg=cfg), donate_argnums=donate)
    return (jitted.lower(params, *args, pool, pool).compile(), pool.shape,
            jnp.dtype(dtype).itemsize)


def _pool_copies(text, pool_shape):
    """The compiled program's copies and slices whose result is the pool or
    one layer of it: ``(op name, shape)`` pairs."""
    dims = [",".join(map(str, sh)) for sh in
            (pool_shape, pool_shape[1:], (1,) + pool_shape[1:])]
    hits = []
    for m in re.finditer(r"^\s*(?:ROOT )?%([\w.\-]+) = \(*((?:f32|bf16)"
                         r"\[([\d,]*)\]\{[^}]*\})", text, re.M):
        name, shape, d = m.groups()
        if d in dims and re.search("copy|slice", name) \
                and "update" not in name:
            hits.append((name, shape))
    return hits


def _entry_layouts(text, pool_shape):
    entry = next(l for l in text.splitlines()
                 if "entry_computation_layout" in l)
    return set(re.findall(
        r"(?:f32|bf16)\[%s\]\{([\d,]*)" % ",".join(map(str, pool_shape)),
        entry))


@pytest.mark.parametrize("arch,heads,head_dim,page", [
    ("gpt2", 16, 64, (8, 128)), ("olmoe", 16, 128, (16, 128)),
    ("ouro", 16, 128, (16, 128))])
@pytest.mark.parametrize("name", ["decode", "chunk", "prefill"])
def test_serving_programs_leave_the_pool_in_place(v5e, name, arch, heads,
                                                  head_dim, page):
    """The guard. A pool whose rows fill the 128 lanes has ONE layout — the
    device's default, the scatter's and the kernel's — so a program takes
    the donated pool, scatters into it and hands it back: no copy, no
    per-layer slice, next to nothing in temporaries. (Rows of 64 lanes:
    four copies of the padded pool a program, three times the pool in
    temporaries; the last test shows this check sees them.) Two formats:
    GPT-2 medium's two heads of 64 a row in fp32, and OLMoE's plain
    ``(16, 128)`` rows (r = 1) in bf16, whose programs also hold the
    experts' grouped-matmul kernels. The decode CHUNK carries the pool
    through a loop on the device: no copy inside or around the loop
    either, and temporaries within 0.1 GB of the single step's. A looped
    stack (Ouro's widths, four passes) carries it through the passes' loop
    too, inside the chunk's, each pass reaching its part of the pool through
    the block table, and holds ONE pass's paged calls."""
    from mxnet_tpu.serving.kv_cache import PageSpec

    spec = PageSpec.lane_dense(2, heads, head_dim, 4 if arch == "ouro" else 1)
    assert spec.k_rows == spec.v_rows == page and not spec.head_major
    compiled, pool_shape, itemsize = _serving_program(v5e, name, page, arch)
    assert spec.shape(pool_shape[1] // spec.parts,
                      pool_shape[2]) == (pool_shape, pool_shape)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    if arch == "ouro":      # a flash or paged call a layer, not a layer and pass
        assert text.count("tpu_custom_call") == 2
        assert text.count(" while(") == (2 if name == "chunk" else 1)
    if arch == "olmoe":     # three grouped matmuls a layer, by their name
        assert len(re.findall(r"%gmm[.\d]* = ", text)) == 6
    assert _pool_copies(text, pool_shape) == []
    assert _entry_layouts(text, pool_shape) == {"4,3,2,1,0"}
    pool_bytes = 2 * itemsize * math.prod(pool_shape)         # K and V
    ma = compiled.memory_analysis()
    # the experts' sorted rows and their float32 products are temporaries
    # of their own (prefill at 512: 4,096 pairs), none the size of the pool
    assert ma.temp_size_in_bytes < pool_bytes / (10 if arch == "gpt2" else 2)
    assert ma.alias_size_in_bytes >= pool_bytes      # donated, taken
    if name == "chunk":
        assert " while(" in text                # one program, a loop
        step = _serving_program(v5e, "decode", page, arch)[0]
        assert ma.temp_size_in_bytes \
            - step.memory_analysis().temp_size_in_bytes < 100 << 20


def test_pool_guard_sees_the_copies_of_a_half_lane_pool(v5e):
    """The same decode program over ``(H, D) = (16, 64)`` rows, as every
    pool was built before PR 25: the guard's checks all fire."""
    compiled, pool_shape, _ = _serving_program(v5e, "decode", (16, 64))
    text = compiled.as_text()
    copies = [n for n, _s in _pool_copies(text, pool_shape)
              if n.startswith("copy")]
    assert len(copies) >= 4, copies
    assert _entry_layouts(text, pool_shape) == {"1,4,3,2,0"}
    pool_bytes = 2 * 4 * math.prod(pool_shape)
    assert compiled.memory_analysis().temp_size_in_bytes > 2 * pool_bytes


def _hybrid_program(chip, name):
    """``model.decode`` (B=64), ``model.decode_chunk`` (the loop over it,
    four rows of results) or ``model.prefill`` (S=1024, or one block: a
    scatter of ONE block copied the window pool in and out, PR 31) of
    Phi-4-mini-flash at published widths, cut to one layer of each kind
    that holds state (mamba, swa, mamba as the memory layer, full, gmu,
    cross), every pool and the state slots donated, compiled for the chip.
    Returns (compiled, the four caches' shapes)."""
    from mxnet_tpu.serving import model as M

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    bf = jnp.bfloat16
    cfg = M.ModelConfig(
        200064, 6, 2560, 40, 10240, 4096, pos="none", bias=False,
        head_dim=64, num_kv_heads=20, window=512, attn_bias=True,
        ffn_gated=True, tie_embed=True, ssm_dt_rank=160,
        layer_kinds=["mamba", "swa", "mamba", "full", "gmu", "cross"])
    bs, g, w = 64, 10, 128
    caches = {"pool": s((1, 2561, g, bs, w), bf),
              "window": s((1, 1153, g, bs, w), bf),
              "conv": s((2, 513, 3 * cfg.d_inner), bf),
              "ssm": s((2, 513, 16, cfg.d_inner), jnp.float32)}
    full, window = cfg.cache_specs()
    assert full.shape(2561, bs) == (caches["pool"].shape,) * 2
    assert window.shape(1153, bs) == (caches["window"].shape,) * 2
    assert full.head_major and full.block_axis == 3
    params = {k: s(v, bf) for k, v in M.param_shapes(cfg).items()}
    aux = ("wk", "wv", "conv", "ssm")
    if name == "decode":
        def fn(params, toks, poss, tables, ctx, kp, vp, wt, slots, *arrays):
            return M.decode(params, toks, poss, tables, ctx, kp, vp, cfg,
                            dict(zip(aux, arrays), wtables=wt, slots=slots))
        args = (s((64,)), s((64,)), s((64, 64)), s((64,)))
        more, donate = (s((64, 64)), s((64,))), (5, 6, 9, 10, 11, 12)
    elif name == "chunk":
        def fn(params, toks, poss, tables, ctx, left, eos, n, kp, vp, wt,
               slots, *arrays):
            return M.decode_chunk(
                params, toks, poss, tables, ctx, left, eos, n, kp, vp, cfg,
                4, dict(zip(aux, arrays), wtables=wt, slots=slots))
        args = (s((64,)), s((64,)), s((64, 64)), s((64,)), s((64,)),
                s((64,)), s(()))
        more, donate = (s((64, 64)), s((64,))), (8, 9, 12, 13, 14, 15)
    else:
        def fn(params, toks, n, table, kp, vp, wt, slot, *arrays):
            return M.prefill(params, toks, n, table, kp, vp, cfg,
                             dict(zip(aux, arrays), wtable=wt, slot=slot))
        S = 64 if name == "prefill-one-block" else 1024
        args = (s((1, S)), s(()), s((S // bs,)))
        more, donate = (s((S // bs,)), s(())), (4, 5, 8, 9, 10, 11)
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        params, *args, caches["pool"], caches["pool"], *more,
        caches["window"], caches["window"], caches["conv"],
        caches["ssm"]).compile()
    return compiled, {k: v.shape for k, v in caches.items()}


@pytest.mark.parametrize("name", ["decode", "chunk", "prefill",
                                  "prefill-one-block"])
def test_hybrid_programs_leave_every_cache_in_place(v5e, name):
    """Twenty K/V heads of 64 are ten page rows of 128 lanes: token-major
    they would be padded to sixteen and copied by every kernel call
    (PR 27); head-major blocks ``(G, bs, W)`` are whole tiles and the
    programs take the full pool, the window pool and the float32 states
    where they lie — no copy or slice the size of one of them or of a
    layer, everything donated and aliased, the state-space kernels by
    their names."""
    from mxnet_tpu.serving.kv_cache import PageSpec

    assert PageSpec.tiled(1, (10, 128)).head_major
    compiled, shapes = _hybrid_program(v5e, name)
    text = compiled.as_text()
    kernel = "ssm_step" if name in ("decode", "chunk") else "ssm_scan"
    assert len(re.findall(r"%%%s[.\d]* = " % kernel, text)) == 2
    # + three attentions (a 64-token prefill's flash forward is the XLA scan)
    assert text.count("tpu_custom_call") >= (
        2 if name == "prefill-one-block" else 5)
    # the conv tails (18 MB of the real 2.6 GB of caches) are the one array
    # the compiler may stage through fast memory around its row scatters
    # (an async slice in, a copy back; PERF.md section 6, PR 31): no layout
    # of theirs is checked here
    for key in ("pool", "window", "ssm"):
        assert _pool_copies(text, shapes[key]) == [], key
    for key in ("pool", "window"):
        assert _entry_layouts(text, shapes[key]) == {"4,3,2,1,0"}
    cache_bytes = (2 * 2 * (math.prod(shapes["pool"])
                            + math.prod(shapes["window"]))
                   + 2 * math.prod(shapes["conv"])
                   + 4 * math.prod(shapes["ssm"]))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= cache_bytes     # donated, all taken
    # the prefill's temporaries are activations of 1,024 tokens, no cache
    assert ma.temp_size_in_bytes < (512 << 20 if "prefill" in name
                                    else 64 << 20)
    if name == "chunk":     # the caches ride a loop on the device
        assert " while(" in text


def _latent_program(chip, name):
    """``model.decode_chunk`` (B=128, eight rows) or ``model.prefill``
    (S=2048) of dots.vlm1's block at published widths, cut to the leading
    dense layer and ONE expert layer (16 of 256 experts held, the shared
    expert), the latent pool of the benchmark's configuration (262,144
    tokens in blocks of 128) donated, compiled for the chip. Returns
    (compiled, the two page arrays' shapes)."""
    from mxnet_tpu.serving import model as M

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    bf, bs, layers = jnp.bfloat16, 128, 2
    cfg = M.ModelConfig(
        16160, layers, 7168, 128, 2048, 3072, norm="rms", pos="rope",
        bias=False, head_dim=128, layer_kinds=["mla"] * layers,
        ffn_gated=True, q_rank=1536, kv_rank=512, rope_dim=64, v_dim=128,
        rope_yarn=(40, 4096, 32, 1, 1, 1), norm_eps=1e-6, first_dense=1,
        dense_ffn_dim=18432, num_experts=256, experts_per_tok=8,
        shared_experts=1, router="sigmoid_group", n_group=8, topk_group=4,
        route_scale=2.5, experts_held=(0, 16))
    full, window = cfg.cache_specs()
    assert (full.k_rows, full.v_rows) == ((1, 512), (1, 128))
    pages = {"k": s((layers, 2049, 1, bs, 512), bf),
             "v": s((layers, 2049, 1, bs, 128), bf)}
    # the kinds this model lacks keep their two-block stand-ins
    stand_ins = (s((1, 2, 1, bs, 512), bf), s((1, 2, 1, bs, 128), bf),
                 s((1, 2, 3 * cfg.d_inner), bf),
                 s((1, 2, 16, cfg.d_inner), jnp.float32))
    assert full.shape(2049, bs) == (pages["k"].shape, pages["v"].shape)
    assert window.shape(2, bs) == tuple(a.shape for a in stand_ins[:2])
    params = {k: s(v, bf) for k, v in M.param_shapes(cfg).items()}
    aux = ("wk", "wv", "conv", "ssm")
    nb = cfg.max_len // bs
    if name == "chunk":
        def fn(params, toks, poss, tables, ctx, left, eos, n, kp, vp, wt,
               slots, *arrays):
            return M.decode_chunk(
                params, toks, poss, tables, ctx, left, eos, n, kp, vp, cfg,
                8, dict(zip(aux, arrays), wtables=wt, slots=slots))
        args = (s((128,)), s((128,)), s((128, nb)), s((128,)), s((128,)),
                s((128,)), s(()))
        more, donate = (s((128, nb)), s((128,))), (8, 9)
    else:
        def fn(params, toks, n, table, kp, vp, wt, slot, *arrays):
            return M.prefill(params, toks, n, table, kp, vp, cfg,
                             dict(zip(aux, arrays), wtable=wt, slot=slot))
        args = (s((1, 2048)), s((2, M.pack_width(cfg, 2048))),
                s((M.pack_blocks(cfg, 2048, bs),)))
        more, donate = (s((M.pack_blocks(cfg, 2048, bs),)), s(())), (4, 5)
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        params, *args, pages["k"], pages["v"], *more, *stand_ins).compile()
    return compiled, {k: v.shape for k, v in pages.items()}


@pytest.mark.parametrize("name", ["chunk", "prefill"])
def test_latent_programs_leave_the_pool_in_place(v5e, name):
    """The latent format — one 512-lane row a token in ``k_pages``, the
    rotary key's 128-lane row in ``v_pages``, head-major — is whole tiles:
    no program copies or slices a page array or a layer of one, both are
    donated and aliased, and the decode kernel and the grouped matmuls
    are on the trace by their names. The prefill's temporaries are a
    2,048-token prompt's (the sorted pairs' buffers are sized ``T x k``
    though a sixteenth is used): with 9.1 GB of weights and the 1.7 GB
    pool they fit the chip."""
    compiled, shapes = _latent_program(v5e, name)
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 3    # one layer's
    if name == "chunk":
        assert len(re.findall(r"%latent_paged[.\d]* = ", text)) == 2
        assert " while(" in text
    else:       # the flash forward over expanded heads, keys 192 wide
        assert text.count("tpu_custom_call") >= 5
    for key in ("k", "v"):
        assert _pool_copies(text, shapes[key]) == [], key
        assert _entry_layouts(text, shapes[key]) == {"4,3,2,1,0"}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * (math.prod(shapes["k"])
                                          + math.prod(shapes["v"]))
    assert ma.temp_size_in_bytes < (1536 << 20 if name == "prefill"
                                    else 64 << 20)


_MIMO_KINDS = ("full", "swa", "swa", "swa", "swa", "swa", "full")


def _gqa_program(chip, name, kinds=_MIMO_KINDS, batch=64, prompt=2048,
                 blocks=5121, chunk=8):
    """``model.decode_chunk`` (B=``batch``) or ``model.prefill``
    (S=``prompt``) of MiMo-V2.5's block at published widths — 64 query
    heads of 192 (64 rotary lanes) over 4 K/V heads in a full layer and 8 in
    a window layer, values of 128, a window of 128 keys with a sink, 16 of
    256 experts held behind a leading dense layer — the benchmark's seven
    layers (``kinds``) and its plan (327,680 tokens of full pool, four
    window blocks a stream), the two pools of UNEQUAL rows donated (K rows
    256 lanes, V rows 128), compiled for the chip. Returns (compiled, the four page arrays'
    shapes)."""
    from mxnet_tpu.serving import model as M

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    bf, bs, layers = jnp.bfloat16, 64, len(kinds)
    cfg = M.ModelConfig(
        19072, layers, 4096, 64, 2048, 8960, norm="rms", pos="rope",
        rope_theta=1e7, bias=False, head_dim=192, layer_kinds=list(kinds),
        attn_form="gqa", num_kv_heads=4, swa_kv_heads=8, swa_rope_theta=1e4,
        rope_dim=64, v_dim=128, window=128, swa_sink=True, value_scale=0.707,
        ffn_gated=True, norm_eps=1e-5, first_dense=1, dense_ffn_dim=16384,
        num_experts=256, experts_per_tok=8, router="sigmoid_group",
        experts_held=(0, 16))
    full, window = cfg.cache_specs()
    assert (full.k_rows, full.v_rows, window.k_rows, window.v_rows) == (
        (4, 256), (4, 128), (8, 256), (8, 128))
    n_full, n_win = kinds.count("full"), kinds.count("swa")
    wblocks = batch * 4 + 1
    pages = {"k": s((n_full, blocks, 4, bs, 256), bf),
             "v": s((n_full, blocks, 4, bs, 128), bf),
             "wk": s((n_win, wblocks, 8, bs, 256), bf),
             "wv": s((n_win, wblocks, 8, bs, 128), bf)}
    assert full.shape(blocks, bs) == (pages["k"].shape, pages["v"].shape)
    assert window.shape(wblocks, bs) == (pages["wk"].shape,
                                         pages["wv"].shape)
    stand_ins = (s((1, 2, 3 * cfg.d_inner), bf),
                 s((1, 2, 16, cfg.d_inner), jnp.float32))
    params = {k: s(v, bf) for k, v in M.param_shapes(cfg).items()}
    aux = ("wk", "wv", "conv", "ssm")
    nb = cfg.max_len // bs
    if name == "chunk":
        def fn(params, toks, poss, tables, ctx, left, eos, n, kp, vp, wt,
               slots, *arrays):
            return M.decode_chunk(
                params, toks, poss, tables, ctx, left, eos, n, kp, vp, cfg,
                chunk, dict(zip(aux, arrays), wtables=wt, slots=slots))
        args = (s((batch,)), s((batch,)), s((batch, nb)), s((batch,)),
                s((batch,)), s((batch,)), s(()))
        more, donate = (s((batch, nb)), s((batch,))), (8, 9, 12, 13)
    else:
        def fn(params, toks, n, table, kp, vp, wt, slot, *arrays):
            return M.prefill(params, toks, n, table, kp, vp, cfg,
                             dict(zip(aux, arrays), wtable=wt, slot=slot))
        args = (s((1, prompt)), s((2, M.pack_width(cfg, prompt))),
                s((M.pack_blocks(cfg, prompt, bs),)))
        more, donate = ((s((M.pack_blocks(cfg, prompt, bs),)), s(())),
                        (4, 5, 8, 9))
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        params, *args, pages["k"], pages["v"], *more, pages["wk"],
        pages["wv"], *stand_ins).compile()
    return compiled, {k: v.shape for k, v in pages.items()}


@pytest.mark.parametrize("name", ["chunk", "prefill"])
def test_gqa_programs_leave_both_pools_in_place(v5e, name):
    """Two pools of unequal rows — four K/V heads a token in the full pool,
    eight in the window pool, and in both a K row of 256 lanes (192 padded
    to whole tiles) beside a V row of 128 — are head-major blocks of whole
    tiles: no program copies or slices a page array or a layer of one, all
    four are donated and aliased. The decode program holds the ONE paged
    kernel under two names (a full walk, a window walk: 16 and 8 query
    lanes a K/V row, the sink as the start state), the prefill the flash
    forward with the K/V head named by the index map (K is not repeated in
    HBM: the temporaries stay a 2,048-token prompt's)."""
    compiled, shapes = _gqa_program(v5e, name)
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 18   # six layers'
    if name == "chunk":
        assert len(re.findall(r"%paged_full_walk[.\d]* = ", text)) == 2
        assert len(re.findall(r"%paged_window_walk[.\d]* = ", text)) == 5
        assert " while(" in text
    else:       # a flash forward a layer, by its name on a trace
        assert len(re.findall(r"%flash_gqa_fwd[.\d]* = ", text)) == 7
        assert text.count("tpu_custom_call") >= 7 + 18
    for key in shapes:
        assert _pool_copies(text, shapes[key]) == [], key
        assert _entry_layouts(text, shapes[key]) == {"4,3,2,1,0"}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * sum(
        math.prod(sh) for sh in shapes.values())
    # the pack's program at 2,048 rows: 557 MiB with each layer's cache
    # writes done before the layer goes on (`attend_gqa`'s barrier), 643 MiB
    # where the compiler puts them off to the program's end (0.37 GB in the
    # top rung: PERF.md section 6, PR 51); a prompt's bare-length form 608
    assert ma.temp_size_in_bytes < (600 << 20 if name == "prefill"
                                    else 96 << 20), ma.temp_size_in_bytes


def _linear_program(chip, name, batch=96, prompt=4096, blocks=4097,
                    chunk=8):
    """``model.decode_chunk`` (B=``batch``) or ``model.prefill``
    (S=``prompt``) of Solar-Open2's block at published widths — three gated
    delta-rule layers (64 heads of 128 keys and 128 values, a conv of 4 taps
    over 24,576 channels) to every gated position-free GQA layer (64 query
    heads over 8 K/V heads of 128), 20 of 320 experts held beside a shared
    one — the benchmark's two periods and its plan (262,144 tokens of full
    pool, 97 slots of six 4 MB float32 states), the pool and both slot
    arrays donated, compiled for the chip. Returns (compiled, the cache
    arrays' shapes)."""
    from mxnet_tpu.serving import model as M

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    bf, bs = jnp.bfloat16, 64
    cfg = M.ModelConfig(
        24576, 8, 4096, 64, 1280, 5120, norm="rms", pos="none", bias=False,
        head_dim=128, num_kv_heads=8, attn_form="gqa", attn_gate=True,
        layer_kinds=["full", "kda", "kda", "kda"] * 2, kda_heads=64,
        kda_head_dim=128, kda_conv=4, kda_neg_eigval=True, ffn_gated=True, norm_eps=1e-5, num_experts=320,
        experts_per_tok=8, shared_experts=1, router="sigmoid_group",
        experts_held=(0, 20))
    full, window = cfg.cache_specs()
    assert (full.k_rows, full.v_rows, full.layers) == ((8, 128), (8, 128), 2)
    conv_width, state_shape = cfg.slot_shapes()
    assert (conv_width, state_shape) == (3 * 24576, (64, 128, 128))
    caches = {"k": s(full.shape(blocks, bs)[0], bf),
              "v": s(full.shape(blocks, bs)[1], bf),
              "conv": s((6, batch + 1, conv_width), bf),
              "ssm": s((6, batch + 1) + state_shape, jnp.float32)}
    stand_ins = tuple(s(sh, bf) for sh in window.shape(2, bs))
    params = {k: s(v, bf) for k, v in M.param_shapes(cfg).items()}
    aux = ("wk", "wv", "conv", "ssm")
    nb = cfg.max_len // bs
    if name == "chunk":
        def fn(params, toks, poss, tables, ctx, left, eos, n, kp, vp, wt,
               slots, *arrays):
            return M.decode_chunk(
                params, toks, poss, tables, ctx, left, eos, n, kp, vp, cfg,
                chunk, dict(zip(aux, arrays), wtables=wt, slots=slots))
        args = (s((batch,)), s((batch,)), s((batch, nb)), s((batch,)),
                s((batch,)), s((batch,)), s(()))
        more, donate = (s((batch, nb)), s((batch,))), (8, 9, 14, 15)
    else:
        def fn(params, toks, n, table, kp, vp, wt, slot, *arrays):
            return M.prefill(params, toks, n, table, kp, vp, cfg,
                             dict(zip(aux, arrays), wtable=wt, slot=slot))
        args = (s((1, prompt)), s(()), s((prompt // bs,)))
        more, donate = (s((prompt // bs,)), s(())), (4, 5, 10, 11)
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        params, *args, caches["k"], caches["v"], *more, *stand_ins,
        caches["conv"], caches["ssm"]).compile()
    return compiled, {k: v.shape for k, v in caches.items()}


@pytest.mark.parametrize("name", ["chunk", "prefill"])
def test_linear_programs_leave_the_pool_and_the_states_in_place(v5e, name):
    """2.4 GB of float32 matrix states (97 slots x 6 layers x 64 heads of
    128 x 128) beside a 2.1 GB pool: no program copies or slices the pool,
    the states or a layer of either (a copy of the states would be a sixth
    of a decode step), all are donated and aliased; the decode chunk's
    eight steps carry them through the loop. The decode program holds a
    ``kda_step`` a linear layer and a ``paged_full_walk`` a GQA layer, the
    prefill a ``kda_chunk`` and a ``flash_gqa_fwd``, by their names on a
    trace."""
    compiled, shapes = _linear_program(v5e, name)
    text = compiled.as_text()
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == 24    # eight layers'
    if name == "chunk":
        assert len(re.findall(r"%kda_step[.\d]* = ", text)) == 6
        assert len(re.findall(r"%paged_full_walk[.\d]* = ", text)) == 2
        assert " while(" in text
    else:
        assert len(re.findall(r"%kda_chunk[.\d]* = ", text)) == 6
        assert len(re.findall(r"%flash_gqa_fwd[.\d]* = ", text)) == 2
    # the conv tails (86 MB) are the one array the compiler may stage around
    # its row scatters, as Phi-4's: no layout of theirs is checked here
    for key in ("k", "v", "ssm"):
        assert _pool_copies(text, shapes[key]) == [], key
    for key in ("k", "v"):
        assert _entry_layouts(text, shapes[key]) == {"4,3,2,1,0"}
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= (
        2 * 2 * math.prod(shapes["k"]) + 2 * math.prod(shapes["conv"])
        + 4 * math.prod(shapes["ssm"]))
    # a 4,096-token prompt's activations (24,576 conv channels of them), no
    # cache; the decode chunk's 96 lanes
    assert ma.temp_size_in_bytes < (1536 << 20 if name == "prefill"
                                    else 256 << 20)


@pytest.mark.parametrize("bs", [64, 128, 256])
def test_latent_kernel_compiles_for_v5e(v5e, bs):
    """The block-size ladder's three rungs, 128 streams of 128 heads."""
    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    n = 262144 // bs + 1
    text = _compiled_text(
        functools.partial(A._latent_pallas, sm_scale=0.135, layer=3),
        s((128, 128, 512)), s((128, 128, 128)), s((5, n, 1, bs, 512)),
        s((5, n, 1, bs, 128)), s((128, 3072 // bs), jnp.int32),
        s((128,), jnp.int32))
    assert len(re.findall(r"%latent_paged[.\d]* = ", text)) == 1


def test_flash_forward_with_two_widths_compiles_for_v5e(v5e):
    def s(d):
        return jax.ShapeDtypeStruct((1, 128, 2048, d), jnp.bfloat16,
                                    sharding=v5e)

    text = _compiled_text(
        functools.partial(A._pallas_forward, causal=True, sm_scale=0.135),
        s(192), s(192), s(128))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads,kv_heads,window", [
    (128, 128, None),       # dots.vlm1: the expanded heads of latent attention
    (64, 4, None),          # MiMo-V2.5's "full" layers
    (64, 8, 128),           # and its "swa" layers: the floor beside the band
], ids=["dotsvlm1", "mimo_full", "mimo_swa"])
def test_flash_forward_with_a_floor_compiles_for_v5e(v5e, heads, kv_heads,
                                                     window):
    """A packed prefill's flash forward — a floor a query row
    (``first_key``), the q blocks' first floors prefetched as scalars, the
    K/V index map clamped to the first block that is read — at the two
    configurations' head widths (keys 192, values 128) in their top rungs'
    neighbourhood: interpret mode cannot see what Mosaic refuses."""
    def s(h, d):
        return jax.ShapeDtypeStruct((1, h, 3072, d), jnp.bfloat16,
                                    sharding=v5e)

    how = {} if heads == kv_heads else {"kv_group": heads // kv_heads,
                                        "name": "flash_gqa_fwd"}
    text = _compiled_text(
        lambda q, k, v, floor: A._pallas_forward(
            q, k, v, True, 0.072, window=window, first_key=floor, **how),
        s(heads, 192), s(kv_heads, 192), s(kv_heads, 128),
        jax.ShapeDtypeStruct((3072,), jnp.int32, sharding=v5e))
    assert "tpu_custom_call" in text
    assert ("flash_gqa_fwd" in text) == bool(how)


def test_the_benchmarks_warm_up_call_warms_the_chunk_program():
    """``benchmark/drivers/serve.py`` warms a decode bucket with the plain
    step's call — ``eng._decode_fn`` with seven arguments, four results —
    and no file of the benchmark may change: that call runs (loads, or
    compiles) the very executable the engine's chunks dispatch, so that
    steps of several decode steps a dispatch compile nothing."""
    import numpy as np

    from mxnet_tpu import compileobs
    from mxnet_tpu.serving import ServingConfig, ServingEngine

    cfg = ServingConfig(vocab_size=23, num_layers=2, model_dim=32,
                        num_heads=2, ffn_dim=48, max_len=64, block_size=8,
                        num_blocks=64, max_batch=4, prefills_per_step=4)
    eng = ServingEngine(cfg, seed=3)
    for S in (8, 16):                            # as `warm_engine` does
        _t, _l, kp, vp = eng._prefill_fn(
            eng.params, np.zeros((1, S), np.int32), np.int32(1),
            np.zeros(S // 8, np.int32), eng.pool.k_pages, eng.pool.v_pages)
        eng.pool.k_pages, eng.pool.v_pages = kp, vp
    for B in cfg.decode_buckets():
        ints = np.zeros(B, np.int32)
        _t, _l, kp, vp = eng._decode_fn(
            eng.params, ints, ints, np.zeros((B, eng._nb_max), np.int32),
            np.ones(B, np.int32), eng.pool.k_pages, eng.pool.v_pages)
        eng.pool.k_pages, eng.pool.v_pages = kp, vp

    def compiles():
        return {p["program"]: p["compile_count"]
                for p in compileobs.program_table()
                if p["program"].startswith("serving.")}

    warm = compiles()
    out = eng.generate([[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11]],
                       [9, 14, 6])
    assert [len(o) for o in out] == [9, 14, 6]
    assert eng.stats()["decode"]["steps_per_dispatch"] > 2
    assert compiles() == warm


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: A.flash_attention(*a, True)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("entry", ["flash_attention", "flash_attention_grad",
                                   "paged_attention",
                                   "paged_attention_multi"])
def test_public_entry_lowers_the_kernel_for_tpu(v5e, entry):
    """No trace-time backend gate: lowered FOR the TPU from a process whose
    default backend is the CPU, the public entry points hold the Pallas
    kernel — and run on the CPU, the same call takes the reference branch."""
    assert jax.default_backend() == "cpu"
    pages = jnp.zeros((3, 16, 2, 64))
    table, ones = jnp.zeros((2, 4), jnp.int32), jnp.ones((2, 5), jnp.int32)
    fn, shapes, small = {
        "flash_attention": (
            functools.partial(A.flash_attention, causal=True),
            _flash_shapes(v5e, 64), [jnp.zeros((1, 2, 128, 64))] * 3),
        "flash_attention_grad": (
            _flash_grad, _flash_shapes(v5e, 64),
            [jnp.zeros((1, 2, 128, 64))] * 3),
        "paged_attention": (
            A.paged_attention, _paged_shapes(v5e, 12, 64, jnp.float32),
            (jnp.zeros((2, 2, 64)), pages, pages, table, ones[:, 0])),
        "paged_attention_multi": (
            A.paged_attention_multi,
            _paged_shapes(v5e, 12, 64, jnp.float32, lanes=5),
            (jnp.zeros((2, 5, 2, 64)), pages, pages, table, ones)),
    }[entry]
    assert "tpu_custom_call" in _compiled_text(fn, *shapes)
    jax.block_until_ready(jax.jit(fn)(*small))


# ------------------------------------------------------------- chip_smoke
def _run(code_or_script, env_extra=None, cwd=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    argv = ([sys.executable, code_or_script] if code_or_script.endswith(".py")
            else [sys.executable, "-c", code_or_script])
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=120)


def test_chip_smoke_fails_fast_without_a_tpu():
    r = _run(os.path.join(ROOT, "chip_smoke.py"),
             env_extra={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert set(last) == {"ok", "device"}


# ---------------------------------------------------------- compile cache
_CACHE_ENVS = ("JAX_COMPILATION_CACHE_DIR", "MXNET_COMPILE_CACHE_DIR")

_CACHE_PROBE = """
import json, os, sys
sys.path.insert(0, %r)
import jax, numpy as np
from mxnet_tpu import compile_cache, compileobs
on_import = compile_cache.cache_dir()
compile_cache.enable(entry_point=True)
f = compileobs.jit(lambda x: x * 2 + 1, "probe", graph_key="probe-graph",
                   aot=True)
f(np.ones(4, np.float32))
print(json.dumps({"on_import": on_import, "dir": compile_cache.cache_dir(),
                  "jax": jax.config.jax_compilation_cache_dir,
                  "stats": compile_cache.stats()}))
""" % ROOT


def _probe(tmp_path, **env):
    r = _run(_CACHE_PROBE, env_extra=env, cwd=str(tmp_path), drop=_CACHE_ENVS)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_dir_follows_jax_env(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins over MXNET_COMPILE_CACHE_DIR, jax's
    own setting is left exactly as the caller gave it, and the AOT
    artifacts and markers land beside jax's files. A second process loads
    the artifact: one hit, no miss, no error — with the rig's eight
    devices, where an executable loaded onto every device would refuse a
    one-device program."""
    d = str(tmp_path / "given")
    env = {"JAX_COMPILATION_CACHE_DIR": d,
           "MXNET_COMPILE_CACHE_DIR": str(tmp_path / "loses")}
    cold = _probe(tmp_path, **env)
    assert cold["on_import"] == cold["dir"] == cold["jax"] == d
    assert len(os.listdir(os.path.join(d, "aot"))) == 1
    assert len(os.listdir(os.path.join(d, "meta"))) == 1
    assert not os.path.exists(os.path.join(d, "jax"))
    assert not os.path.exists(str(tmp_path / "loses"))
    assert (cold["stats"]["misses"], cold["stats"]["errors"]) == (1, 0)
    warm = _probe(tmp_path, **env)
    assert [warm["stats"][k] for k in ("hits", "misses", "errors")] \
        == [1, 0, 0]


def test_cache_dir_default_is_one_fixed_path(tmp_path):
    """Nothing set: a library import stays inert, and an entry point gets
    the same path inside the checkout from any process and any cwd."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from mxnet_tpu import compile_cache as c\n"
            "print(c.enabled(), c.resolve_dir(), "
            "c.resolve_dir(entry_point=True))" % ROOT)
    outs = []
    for cwd in (str(tmp_path), ROOT):
        r = _run(code, cwd=cwd, drop=_CACHE_ENVS)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(r.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1] == "False None %s" % os.path.join(
        ROOT, ".compile_cache")


# ---------------------------------------------------------------- context
class _Dev:
    def __init__(self, platform):
        self.platform = platform

    def __repr__(self):
        return "%s-device" % self.platform


@pytest.fixture
def unpinned():
    """jax as a TPU host has it: not restricted to the CPU platform."""
    assert mx.context._pinned_to_cpu()   # what tests/conftest.py set up
    jax.config.update("jax_platforms", "")
    yield
    jax.config.update("jax_platforms", "cpu")


def test_tpu_context_is_a_host_device_only_under_the_cpu_pin(monkeypatch):
    assert mx.tpu(0).jax_device.platform == "cpu"
    assert mx.gpu(1).jax_device.platform == "cpu"


def test_tpu_context_without_accelerator_raises(monkeypatch, unpinned):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("cpu")])
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(mx.base.MXNetError, match="0 accelerator"):
            ctx.jax_device


def test_tpu_context_past_the_last_chip_raises(monkeypatch, unpinned):
    chip = _Dev("tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    assert mx.tpu(0).jax_device is chip
    with pytest.raises(mx.base.MXNetError, match="1 accelerator"):
        mx.tpu(1).jax_device


def test_num_tpus_does_not_hide_a_backend_failure(monkeypatch):
    def boom(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        mx.context.num_tpus()
