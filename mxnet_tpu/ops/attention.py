"""Attention ops — flash (memory-efficient) multi-head attention.

The reference (MXNet v0.10.1) predates attention entirely — its long-sequence
story is bucketing + fused cuDNN RNNs (SURVEY §5 "Long-context"). This module is
the green-field TPU-first design that gives the framework a modern long-context
path while staying inside the op-registry contract (ops/registry.py).

Design:

* ``flash_attention(q, k, v)`` operates on ``(batch, heads, seq, head_dim)``.
  Forward and backward are the FlashAttention online-softmax algorithm expressed
  as ``lax.scan`` over key/value blocks — O(seq) memory instead of O(seq^2),
  static shapes, MXU-sized matmul blocks. ``jax.custom_vjp`` saves only
  ``(q, k, v, out, lse)`` residuals; the backward pass is the standard
  dq/dk/dv block recurrence (recompute-based, no S matrix ever materialised).
* On TPU the forward uses a Pallas kernel (``_pallas_forward``) with 512×1024
  q/kv blocks (measured 12.7 TFLOP/s at seq 4096 on v5e — 1.9x XLA's scan
  lowering and 1.8x the jax library flash kernel; tiny blocks starve the MXU);
  everywhere else (CPU tests, odd shapes) the pure-XLA scan path runs. Both
  produce identical (out, lse) residuals so the backward is shared.
* The op is registered as ``_contrib_FlashAttention`` so it is reachable from
  both ``mx.nd.contrib.FlashAttention`` and ``mx.sym.contrib.FlashAttention``
  (the escape-hatch naming the reference uses for new ops, SURVEY §2.3 contrib).
* Ring/Ulysses sequence parallelism (parallel/ring.py) reuses the same block
  kernel: a ring step is one ``_block_update`` against a remote KV shard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import Param, fp32_precision, register

__all__ = ["flash_attention", "flash_attention_gqa", "attention_reference",
           "paged_attention",
           "paged_attention_reference", "paged_attention_multi",
           "paged_attention_multi_reference", "latent_paged",
           "latent_paged_reference"]

_NEG_INF = -1e30


def _scale(sm_scale, d):
    return 1.0 / np.sqrt(d) if sm_scale is None else sm_scale


def attention_reference(q, k, v, causal=False, sm_scale=None, window=None,
                        first_key=None):
    """Naive softmax attention — the numeric oracle for tests (O(S^2) memory).
    ``window`` / ``first_key`` (Sq,): key j is visible to query i only if
    ``i - j < window`` / ``j >= first_key[i]``."""
    sm_scale = _scale(sm_scale, q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * sm_scale
    qi = jnp.arange(q.shape[2])[:, None]
    ki = jnp.arange(k.shape[2])[None, :]
    if causal:
        s = jnp.where(qi >= ki, s, _NEG_INF)
    if window is not None:
        s = jnp.where(qi - ki < window, s, _NEG_INF)
    if first_key is not None:
        s = jnp.where(ki >= first_key[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST).astype(q.dtype)


# ------------------------------------------------------------------ block math
def _block_update(q, k_blk, v_blk, m, l, acc, sm_scale, mask=None,
                  precision=None):
    """One online-softmax update of (m, l, acc) with a KV block.

    q: (B,H,Sq,D) f32; k_blk/v_blk: (B,H,Bk,D); m,l: (B,H,Sq); acc: (B,H,Sq,D).
    mask: optional (Sq, Bk) bool — True = attend. precision: MXU precision
    chosen from the ORIGINAL (pre-cast) input dtype, see fp32_precision().
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32,
                   precision=precision) * sm_scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(s - m_new[..., None])
    scale = jnp.exp(m - m_new)
    l_new = l * scale + jnp.sum(p, axis=-1)
    acc_new = acc * scale[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_blk, preferred_element_type=jnp.float32,
        precision=precision
    )
    return m_new, l_new, acc_new


def _scan_forward(q, k, v, causal, sm_scale, block_k, window=None,
                  first_key=None):
    """Pure-XLA flash forward: lax.scan over KV blocks. Returns (out, lse) f32.
    ``window``: key j is visible to query i only if ``i - j < window``.
    ``first_key`` (Sq,) int32: and only if ``j >= first_key[i]``.
    The values may be of another width than the keys."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    block_k = min(block_k, sk)
    n_blk = -(-sk // block_k)
    pad = n_blk * block_k - sk
    prec = fp32_precision(q.dtype)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # (n_blk, B, H, block_k, D) scan-major layout
    kb = jnp.moveaxis(kf.reshape(b, h, n_blk, block_k, d), 2, 0)
    vb = jnp.moveaxis(vf.reshape(b, h, n_blk, block_k, dv), 2, 0)
    qi = jnp.arange(sq)

    def step(carry, xs):
        m, l, acc = carry
        k_blk, v_blk, blk_idx = xs
        ki = blk_idx * block_k + jnp.arange(block_k)
        mask = ki[None, :] < sk  # (1, Bk) padding mask
        if causal:
            mask = mask & (qi[:, None] >= ki[None, :])
        else:
            mask = jnp.broadcast_to(mask, (sq, block_k))
        if window is not None:
            mask = mask & (qi[:, None] - ki[None, :] < window)
        if first_key is not None:
            mask = mask & (ki[None, :] >= first_key[:, None])
        m, l, acc = _block_update(qf, k_blk, v_blk, m, l, acc, sm_scale, mask,
                                  precision=prec)
        return (m, l, acc), None

    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, acc0), (kb, vb, jnp.arange(n_blk)))
    l = jnp.maximum(l, 1e-30)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse


def _pallas_forward(q, k, v, causal, sm_scale, block_q=512, block_k=1024,
                    interpret=False, window=None, kv_group=1, name=None,
                    first_key=None):
    """Pallas TPU flash-attention forward.

    ``window`` (sliding-window attention): key j is visible to query i only
    if ``i - j < window``; KV blocks wholly behind a q block's band are
    skipped like those above the diagonal, and the KV block is ``block_q``
    long so that the band skips something.

    ``first_key`` (Sq,) int32, a floor a query row that does not decrease
    along the rows (several texts end to end, each row's floor its own
    text's first row): key j is visible to query i only if ``j >=
    first_key[i]``, of which ``window`` is the case ``i - window + 1``; both
    hold together. KV blocks wholly below the floor of a q block's FIRST
    row are skipped like those above the diagonal, and are not fetched (the
    index map names the first block that is read in their place), so the
    texts cost about the sum of their own attention and not the square of
    their sum; the KV block is ``block_q`` long here too.

    Grid (batch*heads, q_blocks, kv_blocks) with the KV axis innermost: TPU
    executes the grid sequentially along the last axis, so (m, l, acc) live in
    VMEM scratch carried across KV steps — per-core VMEM is O(block_q·d +
    block_k·d), independent of sequence length. Output is written on the last
    KV step. Returns (out, lse) float32, identical residuals to
    ``_scan_forward``. The values may be of another width ``dv`` than the
    keys (latent attention's expanded heads: keys 192, values 128).

    ``kv_group`` > 1 (grouped queries): k and v are ``(B, H / kv_group, S,
    .)`` and the index map names query head i's K/V head, ``i //
    kv_group``: a K/V head is not written ``kv_group`` times to HBM first.
    ``name``: the custom call's name on a trace (without one it is
    ``branch_0_fun``, as every earlier call's). The defaults leave the
    program every earlier caller traced as it was.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    floored = first_key is not None
    if window is not None or floored:
        block_k = min(block_k, block_q)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q = -(-sq // block_q)  # ragged tails are masked inside the kernel
    n_k = -(-sk // block_k)

    def kernel(*refs):
        if floored:     # the q blocks' first floors (SMEM), the rows' own
            floor0_ref, q_ref, k_ref, v_ref, floor_ref, *refs = refs
        else:
            q_ref, k_ref, v_ref, *refs = refs
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        qi_blk = pl.program_id(1)
        kj = pl.program_id(2)

        @pl.when(kj == 0)
        def _init():
            m_ref[:] = jnp.full((block_q,), _NEG_INF, jnp.float32)
            l_ref[:] = jnp.zeros((block_q,), jnp.float32)
            acc_ref[:] = jnp.zeros((block_q, dv), jnp.float32)

        # causal: skip blocks strictly above the diagonal
        first_q_pos = qi_blk * block_q + block_q - 1  # last row of the q block
        run = (kj * block_k <= first_q_pos) if causal else True
        if window is not None:
            # the block's last key is inside the first row's window
            run = run & (kj * block_k + block_k - 1
                         > qi_blk * block_q - window)
        if floored:
            # the block's last key is at or above the first row's floor
            run = run & (kj * block_k + block_k - 1 >= floor0_ref[qi_blk])

        @pl.when(run)
        def _step():
            qv = q_ref[0].astype(jnp.float32)
            kv = k_ref[0].astype(jnp.float32)
            vv = v_ref[0].astype(jnp.float32)
            s = jnp.dot(qv, kv.T, preferred_element_type=jnp.float32) * sm_scale
            q_pos = qi_blk * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = k_pos < sk
            if causal:
                mask = mask & (q_pos >= k_pos)
            if window is not None:
                mask = mask & (q_pos - k_pos < window)
            if floored:
                mask = mask & (k_pos >= floor_ref[0, 0][:, None])
            s = jnp.where(mask, s, _NEG_INF)
            m = m_ref[:]
            m_blk = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, m_blk)
            p = jnp.exp(s - m_new[:, None])
            scale = jnp.exp(m - m_new)
            m_ref[:] = m_new
            l_ref[:] = l_ref[:] * scale + jnp.sum(p, axis=-1)
            acc_ref[:] = acc_ref[:] * scale[:, None] + jnp.dot(
                p, vv, preferred_element_type=jnp.float32
            )

        @pl.when(kj == n_k - 1)
        def _finish():
            l = jnp.maximum(l_ref[:], 1e-30)
            o_ref[0] = acc_ref[:] / l[:, None]
            lse_ref[0] = (m_ref[:] + jnp.log(l))[None, :]

    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh // kv_group, sk, d)
    vr = v.reshape(bh // kv_group, sk, dv)
    pad_q = n_q * block_q - sq
    pad_k = n_k * block_k - sk
    if pad_q:
        qr = jnp.pad(qr, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kr = jnp.pad(kr, ((0, 0), (0, pad_k), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pad_k), (0, 0)))
    grid = (bh, n_q, n_k)

    def kv_at(i, j, kk, *floor0):
        if floored:     # a block below the floor: the first that is read
            kk = jnp.maximum(kk, floor0[0][j] // block_k)
        return (i // kv_group if kv_group > 1 else i), kk, 0

    def q_at(i, j, kk, *_floor0):
        return i, j, 0

    def row_at(i, j, kk, *_floor0):
        return i, 0, j

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_at),
        pl.BlockSpec((1, block_k, d), kv_at),
        pl.BlockSpec((1, block_k, dv), kv_at),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, dv), q_at),
        pl.BlockSpec((1, 1, block_q), row_at),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q, dv), jnp.float32),
    ]
    operands = (qr, kr, vr)
    if floored:
        # the rows' floors as the lse leaves: a q block's a lane vector;
        # the padded tail keeps the last row's (the floors do not decrease)
        floors = jnp.pad(first_key.astype(jnp.int32), (0, pad_q), mode="edge")
        in_specs.append(pl.BlockSpec(
            (1, 1, block_q), lambda i, j, kk, _floor0: (0, 0, j)))
        operands = (floors[::block_q],) + operands + (floors[None, None],)
        how = {"grid_spec": pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes)}
    else:
        how = {"grid": grid, "in_specs": in_specs, "out_specs": out_specs,
               "scratch_shapes": scratch_shapes}
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_q * block_q, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, n_q * block_q), jnp.float32),
        ],
        interpret=interpret,
        **how,
        **({} if name is None else {"name": name}),
    )(*operands)
    out = out[:, :sq].reshape(b, h, sq, dv)
    lse = lse[:, 0, :sq].reshape(b, h, sq)
    return out, lse


def _pallas_shapes_ok(q, k):
    """Shapes the Pallas kernel handles; platform choice happens separately
    at lowering time (lax.platform_dependent in _forward_impl). Ragged block
    tails are masked inside the kernel, but hardware Mosaic wants the
    second-minor tile aligned — require sequence multiples of 128 on the
    Pallas path; anything else takes the scan lowering."""
    d = q.shape[-1]
    # Mosaic pads the lane dim, so any multiple of 8 works; 64 is the common
    # head_dim and must not fall back to the scan path
    return (d % 8 == 0 and q.shape[2] % 128 == 0 and k.shape[2] % 128 == 0
            and q.shape[2] >= 128 and k.shape[2] >= 128)


def _pallas_backward(q, k, v, out, lse, g, causal, sm_scale,
                     block_q=512, block_k=512, interpret=False):
    """Pallas TPU flash-attention backward — two kernels, each recomputing P
    from the saved lse (no S matrix materialised, same residuals as the scan
    path): dk/dv iterate q-blocks innermost with the (block_k, d) accumulators
    in VMEM; dq iterates kv-blocks innermost. delta = rowsum(dout*out) is
    precomputed in XLA."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q = -(-sq // block_q)
    n_k = -(-sk // block_k)
    bh = b * h

    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)

    def prep(x, s, pad_to):
        x = x.reshape(bh, s, -1)
        pad = pad_to - s
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    qr = prep(q, sq, n_q * block_q)
    gr = prep(g, sq, n_q * block_q)
    kr = prep(k, sk, n_k * block_k)
    vr = prep(v, sk, n_k * block_k)
    lse_r = prep(lse[..., None], sq, n_q * block_q)[..., 0].reshape(bh, 1, -1)
    delta_r = prep(delta[..., None], sq, n_q * block_q)[..., 0].reshape(bh, 1, -1)

    def recompute(qv, gv, kv, vv, lse_row, delta_row, qi_blk, kj):
        s = jnp.dot(qv, kv.T, preferred_element_type=jnp.float32) * sm_scale
        q_pos = qi_blk * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < sk
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_row[:, None])  # (bq, bk); 0 where masked
        dp = jnp.dot(gv, vv.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_row[:, None]) * sm_scale
        return p, ds

    def kernel_dkv(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc):
        kj = pl.program_id(1)
        qi_blk = pl.program_id(2)

        @pl.when(qi_blk == 0)
        def _init():
            dk_acc[:] = jnp.zeros((block_k, d), jnp.float32)
            dv_acc[:] = jnp.zeros((block_k, d), jnp.float32)

        run = (qi_blk * block_q + block_q - 1 >= kj * block_k) if causal else True

        @pl.when(run)
        def _step():
            qv = q_ref[0].astype(jnp.float32)
            gv = g_ref[0].astype(jnp.float32)
            kv = k_ref[0].astype(jnp.float32)
            vv = v_ref[0].astype(jnp.float32)
            p, ds = recompute(qv, gv, kv, vv, lse_ref[0, 0], delta_ref[0, 0],
                              qi_blk, kj)
            dv_acc[:] += jnp.dot(p.T, gv, preferred_element_type=jnp.float32)
            dk_acc[:] += jnp.dot(ds.T, qv, preferred_element_type=jnp.float32)

        @pl.when(qi_blk == n_q - 1)
        def _finish():
            dk_ref[0] = dk_acc[:]
            dv_ref[0] = dv_acc[:]

    def kernel_dq(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                  dq_ref, dq_acc):
        qi_blk = pl.program_id(1)
        kj = pl.program_id(2)

        @pl.when(kj == 0)
        def _init():
            dq_acc[:] = jnp.zeros((block_q, d), jnp.float32)

        run = (kj * block_k <= qi_blk * block_q + block_q - 1) if causal else True

        @pl.when(run)
        def _step():
            qv = q_ref[0].astype(jnp.float32)
            gv = g_ref[0].astype(jnp.float32)
            kv = k_ref[0].astype(jnp.float32)
            vv = v_ref[0].astype(jnp.float32)
            _, ds = recompute(qv, gv, kv, vv, lse_ref[0, 0], delta_ref[0, 0],
                              qi_blk, kj)
            dq_acc[:] += jnp.dot(ds, kv, preferred_element_type=jnp.float32)

        @pl.when(kj == n_k - 1)
        def _finish():
            dq_ref[0] = dq_acc[:]

    q_spec = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, kk, 0))
    kv_spec_outer = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda i, j, kk: (i, 0, kk))
    dk, dv = pl.pallas_call(
        kernel_dkv,
        grid=(bh, n_k, n_q),
        in_specs=[q_spec, q_spec, kv_spec_outer, kv_spec_outer, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_k * block_k, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, n_k * block_k, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qr, gr, kr, vr, lse_r, delta_r)

    q_spec2 = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0))
    kv_spec2 = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q), lambda i, j, kk: (i, 0, j))
    (dq,) = pl.pallas_call(
        kernel_dq,
        grid=(bh, n_q, n_k),
        in_specs=[q_spec2, q_spec2, kv_spec2, kv_spec2, row_spec2, row_spec2],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, n_q * block_q, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qr, gr, kr, vr, lse_r, delta_r)

    dq = dq[:, :sq].reshape(b, h, sq, d).astype(q.dtype)
    dk = dk[:, :sk].reshape(b, h, sk, d).astype(k.dtype)
    dv = dv[:, :sk].reshape(b, h, sk, d).astype(v.dtype)
    return dq, dk, dv


def _scan_backward(q, k, v, out, lse, g, causal, sm_scale, block_k):
    """Flash backward: recompute P per block from saved lse; accumulate dq/dk/dv."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_k = min(block_k, sk)
    n_blk = -(-sk // block_k)
    pad = n_blk * block_k - sk
    prec = fp32_precision(q.dtype)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    gf = g.astype(jnp.float32)
    of = out.astype(jnp.float32)
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = jnp.moveaxis(kf.reshape(b, h, n_blk, block_k, d), 2, 0)
    vb = jnp.moveaxis(vf.reshape(b, h, n_blk, block_k, d), 2, 0)
    delta = jnp.sum(of * gf, axis=-1)  # (B,H,Sq)
    qi = jnp.arange(sq)

    def step(dq, xs):
        k_blk, v_blk, blk_idx = xs
        ki = blk_idx * block_k + jnp.arange(block_k)
        mask = ki[None, :] < sk
        if causal:
            mask = mask & (qi[:, None] >= ki[None, :])
        else:
            mask = jnp.broadcast_to(mask, (sq, block_k))
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk, preferred_element_type=jnp.float32,
                       precision=prec) * sm_scale
        s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B,H,Sq,Bk)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, gf, preferred_element_type=jnp.float32,
                            precision=prec)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_blk, preferred_element_type=jnp.float32,
                        precision=prec)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk, preferred_element_type=jnp.float32,
                             precision=prec)
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf, preferred_element_type=jnp.float32,
                            precision=prec)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
    dq, (dk_b, dv_b) = lax.scan(step, dq0, (kb, vb, jnp.arange(n_blk)))
    dk = jnp.moveaxis(dk_b, 0, 2).reshape(b, h, n_blk * block_k, d)[:, :, :sk]
    dv = jnp.moveaxis(dv_b, 0, 2).reshape(b, h, n_blk * block_k, d)[:, :, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, sm_scale=None, block_k=256,
                    window=None, first_key=None):
    """Memory-efficient attention over (batch, heads, seq, head_dim).
    ``window`` (forward only): key j is visible to query i only if
    ``i - j < window`` — with ``causal``, a sliding window of ``window``
    keys that ends at the query's own. ``first_key`` (seq,) int32 (forward
    only): and only if ``j >= first_key[i]``, a floor a query row that does
    not decrease along the rows — several texts end to end in one call,
    each attending to itself alone. None is the program there always was."""
    out, _ = _forward_impl(q, k, v, causal, sm_scale, block_k, window,
                           first_key)
    return out


def _with_floor(fn, first_key, **kw):
    """``(fn with kw bound, the operands behind q, k and v)``: a floor rides
    as a fourth array operand (``lax.platform_dependent``'s branches take
    arrays alone); without one ``fn`` is the partial it always was."""
    if first_key is None:
        return functools.partial(fn, **kw), ()
    return (lambda q, k, v, floor: fn(q, k, v, first_key=floor, **kw),
            (first_key,))


def _forward_impl(q, k, v, causal, sm_scale, block_k, window=None,
                  first_key=None):
    sm_scale = _scale(sm_scale, q.shape[-1])
    if window is not None or first_key is not None:
        kw = {"causal": causal, "sm_scale": sm_scale,
              "window": None if window is None else int(window)}
        scan, floor = _with_floor(_scan_forward, first_key, block_k=block_k,
                                  **kw)
        if _pallas_shapes_ok(q, k):
            out, lse = lax.platform_dependent(
                q, k, v, *floor, default=scan,
                tpu=_with_floor(_pallas_forward, first_key, **kw)[0])
        else:
            out, lse = scan(q, k, v, *floor)
    elif _pallas_shapes_ok(q, k):
        # platform selected at LOWERING time, not trace time: the same traced
        # function may compile for the TPU (Pallas kernel) or for CPU (scan) —
        # an array's placement isn't knowable from a tracer
        out, lse = lax.platform_dependent(
            q, k, v,
            tpu=functools.partial(_pallas_forward, causal=causal, sm_scale=sm_scale),
            default=functools.partial(_scan_forward, causal=causal,
                                      sm_scale=sm_scale, block_k=block_k),
        )
    else:
        out, lse = _scan_forward(q, k, v, causal, sm_scale, block_k)
    return out.astype(q.dtype), lse


def _fa_fwd(q, k, v, causal, sm_scale, block_k, window, first_key=None):
    out, lse = _forward_impl(q, k, v, causal, sm_scale, block_k, window,
                             first_key)
    return out, (q, k, v, out, lse, first_key)


def _fa_bwd(causal, sm_scale, block_k, window, res, g):
    q, k, v, out, lse, first_key = res
    if window is not None or first_key is not None:
        raise NotImplementedError(
            "flash_attention(window=) and (first_key=) are forward-only "
            "(serving prefill)")
    scale = _scale(sm_scale, q.shape[-1])
    if _pallas_shapes_ok(q, k):
        grads = lax.platform_dependent(
            q, k, v, out, lse, g,
            tpu=functools.partial(_pallas_backward, causal=causal, sm_scale=scale),
            default=functools.partial(_scan_backward, causal=causal,
                                      sm_scale=scale, block_k=block_k),
        )
    else:
        grads = _scan_backward(q, k, v, out, lse, g, causal, scale, block_k)
    return tuple(grads) + (None,)       # first_key: None has no cotangent


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_gqa(q, k, v, sm_scale=None, window=None, sink=None,
                        block_k=256, first_key=None):
    """Causal grouped-query attention, forward only (serving prefill):
    q ``(B, H, S, D)`` over k ``(B, Hkv, S, D)`` and v ``(B, Hkv, S, Dv)``,
    query head j reading K/V head ``j // (H / Hkv)``.

    ``window``: key j is visible to query i only if ``i - j < window``.
    ``first_key`` (S,) int32: and only if ``j >= first_key[i]``
    (:func:`flash_attention`'s; None is the program there always was).
    ``sink`` (H,) float32: a scalar a query head that joins the softmax's
    denominator and no numerator, ``p_ij = exp(s_ij) / (exp(b) + sum_j'
    exp(s_ij'))``: the plain result times ``sigmoid(lse - b)``.

    On the TPU the Pallas forward with the K/V head named by the index map
    (K is not repeated in HBM; a window's blocks wholly behind the band are
    skipped), its custom call named ``flash_gqa_fwd`` on a trace; elsewhere
    the scan over repeated K/V."""
    sm_scale = _scale(sm_scale, q.shape[-1])
    group = q.shape[1] // k.shape[1]
    kw = {"causal": True, "sm_scale": sm_scale,
          "window": None if window is None else int(window)}

    def repeated(q, k, v, **floor):
        return _scan_forward(q, jnp.repeat(k, group, axis=1),
                             jnp.repeat(v, group, axis=1), block_k=block_k,
                             **kw, **floor)

    scan, floor = _with_floor(repeated, first_key)
    if _pallas_shapes_ok(q, k):
        out, lse = lax.platform_dependent(
            q, k, v, *floor, default=scan,
            tpu=_with_floor(_pallas_forward, first_key, kv_group=group,
                            name="flash_gqa_fwd", **kw)[0])
    else:
        out, lse = scan(q, k, v, *floor)
    if sink is not None:
        out = out * jax.nn.sigmoid(lse - sink[None, :, None])[..., None]
    return out.astype(q.dtype)


# ------------------------------------------------------------- registered ops
@register(
    "_contrib_FlashAttention",
    arg_names=("query", "key", "value"),
    params={
        "causal": Param.bool(False),
        "sm_scale": Param.float(-1.0),
    },
)
def _flash_attention_op(octx, attrs, args, auxs):
    q, k, v = args
    scale = attrs["sm_scale"]
    out = flash_attention(q, k, v, attrs["causal"], None if scale <= 0 else scale)
    return [out], []


@register(
    "_contrib_MultiHeadAttention",
    arg_names=("data", "in_weight", "out_weight"),
    params={
        "num_heads": Param.int(),
        "causal": Param.bool(True),
    },
)
def _mha_op(octx, attrs, args, auxs):
    """Self-attention block over (batch, seq, model): fused qkv projection +
    flash attention + output projection. in_weight: (3*model, model),
    out_weight: (model, model) — weights laid out like FullyConnected (out, in)."""
    x, w_in, w_out = args
    bsz, seq, model = x.shape
    heads = attrs["num_heads"]
    hd = model // heads
    prec = fp32_precision(x.dtype)
    qkv = jnp.einsum("bsm,nm->bsn", x, w_in, precision=prec)  # (B,S,3*model)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def split_heads(t):
        return t.reshape(bsz, seq, heads, hd).transpose(0, 2, 1, 3)

    out = flash_attention(split_heads(q), split_heads(k), split_heads(v), attrs["causal"])
    out = out.transpose(0, 2, 1, 3).reshape(bsz, seq, model)
    return [jnp.einsum("bsm,nm->bsn", out, w_out, precision=prec)], []


def _mha_infer_shape(attrs, in_shapes, aux_shapes):
    data = in_shapes[0]
    if data is None:
        raise ValueError("MultiHeadAttention: data shape required")
    model = data[2]
    if in_shapes[1] is None:
        in_shapes[1] = (3 * model, model)
    if in_shapes[2] is None:
        in_shapes[2] = (model, model)
    return in_shapes, [tuple(data)], []


from .registry import get_op  # noqa: E402

get_op("_contrib_MultiHeadAttention")._infer_shape = _mha_infer_shape


# ---------------------------------------------------- incremental decoding
@register(
    "_contrib_CachedMultiHeadAttention",
    arg_names=("data", "in_weight", "out_weight", "position"),
    aux_names=("cache_k", "cache_v"),
    params={
        "num_heads": Param.int(),
        "max_len": Param.int(),
    },
)
def _cached_mha_op(octx, attrs, args, auxs):
    """One autoregressive decode step with static-shape KV caches.

    Not in the reference (its era predates attention serving); this is the
    TPU-idiomatic incremental decoder: caches are AUX STATES of fixed shape
    (batch, heads, max_len, head_dim) mutated in place each step (the same
    FMutateInputs mechanism BatchNorm's moving stats use), so every step
    compiles once and replays — no per-length recompilation, the KV-cache
    analog of the paged-attention serving pattern.

    data: (B, 1, model) — the current token's hidden state;
    position: (1,) float — the step index t (tokens 0..t-1 already cached).
    Returns (B, 1, model); writes the step's k/v into the caches at t.

    Graph-level overflow contract: a position >= max_len can NEVER corrupt
    the cache — the write is dropped (both caches pass through unchanged)
    and the op's output is poisoned to NaN so the overflow fails loudly at
    the consumer instead of silently rereading a clobbered slot. (XLA admits
    no data-dependent errors, so in-graph the hazard lowers to
    drop-write + poison; ``transformer_lm.decode_step`` still raises
    host-side before dispatch.)
    """
    x, w_in, w_out, position = args
    cache_k, cache_v = auxs
    bsz, one, model = x.shape
    heads = attrs["num_heads"]
    max_len = attrs["max_len"]
    hd = model // heads
    pos_raw = position.reshape(()).astype(jnp.int32)
    in_range = (pos_raw >= 0) & (pos_raw < max_len)
    pos = jnp.clip(pos_raw, 0, max_len - 1)  # safe index for the dropped write

    prec = fp32_precision(x.dtype)
    qkv = jnp.einsum("bsm,nm->bsn", x, w_in, precision=prec)  # (B, 1, 3*model)
    q, k_new, v_new = jnp.split(qkv, 3, axis=-1)

    def heads_first(t):
        return t.reshape(bsz, 1, heads, hd).transpose(0, 2, 1, 3)  # (B,H,1,hd)

    q, k_new, v_new = heads_first(q), heads_first(k_new), heads_first(v_new)
    new_k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                         (0, 0, pos, 0))
    new_v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                         (0, 0, pos, 0))
    # overflow contract: out-of-range positions drop the write entirely
    new_k = jnp.where(in_range, new_k, cache_k)
    new_v = jnp.where(in_range, new_v, cache_v)
    # attend q over positions <= t
    s = jnp.einsum("bhqd,bhkd->bhqk", q, new_k,
                   preferred_element_type=jnp.float32,
                   precision=prec) / np.sqrt(hd)
    valid = jnp.arange(max_len) <= pos
    s = jnp.where(valid[None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(new_v.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, new_v, precision=prec)  # (B,H,1,hd)
    out = out.transpose(0, 2, 1, 3).reshape(bsz, 1, model)
    out = jnp.einsum("bsm,nm->bsn", out, w_out, precision=prec)
    # overflow contract: poison the output so an out-of-range step fails
    # loudly downstream instead of returning stale-slot attention
    out = jnp.where(in_range, out, jnp.asarray(np.nan, out.dtype))
    return [out], [new_k, new_v]


def _cached_mha_infer(attrs, in_shapes, aux_shapes):
    data = in_shapes[0]
    if data is None:
        raise ValueError("CachedMultiHeadAttention: data shape required")
    b, one, model = data
    heads = attrs["num_heads"]
    max_len = attrs["max_len"]
    hd = model // heads
    if in_shapes[1] is None:
        in_shapes[1] = (3 * model, model)
    if in_shapes[2] is None:
        in_shapes[2] = (model, model)
    if in_shapes[3] is None:
        in_shapes[3] = (1,)
    cache = (b, heads, max_len, hd)
    return in_shapes, [tuple(data)], [cache, cache]


get_op("_contrib_CachedMultiHeadAttention")._infer_shape = _cached_mha_infer


# ------------------------------------------------------- paged (ragged) decode
# Page format. A page row is ``(G, W)``: ``r = W // D`` consecutive heads of
# ``D`` lanes side by side, ``G = H // r`` rows (``serving/kv_cache.py``
# ``PageSpec.lane_dense`` picks r so that W fills the TPU's 128 lanes;
# r = 1 is the plain ``(H, D)`` row). ``(H, D) -> (G, W)`` is a row-major
# reshape, so q, the new K/V rows and the output are packed and unpacked by
# reshapes at the edges and r is read off the shapes. Pages come as one
# layer's ``(N, bs, G, W)`` or as the whole pool ``(L, N, bs, G, W)`` with a
# static ``layer``: the kernels address the pool in place, because a
# ``k_pages[layer]`` slice is a copy of that layer on every step.
#
# ``head_major`` pages are ``(.., N, G, bs, W)``: a block holds each of its G
# rows as a ``(bs, W)`` slab, whole tiles for ANY G (ten rows of 128 lanes
# would be padded to sixteen in ``(bs, G, W)`` order). They take r = 1 only:
# a row is one head of W lanes. Grouped queries (several query heads reading
# one K/V row) need no format of their own: they ride as extra query lanes
# with the same context length.
def _heads_per_row(q, k_pages, head_major=False):
    """r for q ``(.., H, D)`` against pages ``(.., bs, G, W)``, or
    ``(.., G, bs, W)`` if ``head_major``."""
    (h, d), (g, w) = q.shape[-2:], k_pages.shape[-2:]
    if head_major:
        g = k_pages.shape[-3]
        if (g, w) != (h, d):
            raise ValueError("head-major pages of %d rows of %d cannot hold "
                             "q's %d heads of %d" % (g, w, h, d))
        return 1
    r = w // d
    if (g * r, r * d) != (h, w):
        raise ValueError("pages with rows %s cannot hold q's %d heads of %d"
                         % ((g, w), h, d))
    return r


def _whole_pool(k_pages, v_pages, layer):
    """5-D pages and a layer index from either form."""
    if k_pages.ndim == 4:
        return k_pages[None], v_pages[None], 0
    if layer is None:
        raise ValueError("a 5-D page pool needs layer=")
    return k_pages, v_pages, int(layer)


def _gather_tokens(pages, block_tables, h, d, head_major=False):
    """Each sequence's pages (N, bs, G, W) in position order, unpacked:
    (B, T, H, D) f32; head-major pages' rows are ``W`` wide whatever ``d``
    (V pages may be narrower than K pages)."""
    b, nb = block_tables.shape
    x = jnp.take(pages, block_tables, axis=0)       # (B, nb, bs, G, W)
    if head_major:                                  # (B, nb, G, bs, W)
        x = x.transpose(0, 1, 3, 2, 4)
        d = pages.shape[-1]
    return x.reshape(b, -1, h, d).astype(jnp.float32)


def paged_attention_reference(q, k_pages, v_pages, block_tables, context_lens,
                              sm_scale=None, layer=None):
    """Pure-XLA paged decode attention — the numeric oracle and the CPU/CI
    lowering of the Pallas kernel below.

    One query token per sequence attends over a block-paged ragged KV cache
    (the "Ragged Paged Attention" serving layout, PAPERS.md): sequences own
    fixed-size blocks of a shared pool, named by a per-sequence block table.

    q:            (B, H, D)        — this step's query, one token per stream
    k_pages:      (N, bs, G, W)    — the shared K pool: N blocks of bs slots,
                                     or (L, N, bs, G, W) with ``layer``
    v_pages:      the shared V pool, same shape
    block_tables: (B, nb) int32    — block ids per sequence, in position
                                     order; unused tail entries may point at
                                     any block (masked by context_lens)
    context_lens: (B,) int32       — valid tokens per sequence (<= nb*bs)

    Returns (B, H, D) in q.dtype. Positions >= context_len contribute
    EXACTLY zero: their scores are pinned to -1e30, which underflows to
    p = 0.0 in float32 — garbage in masked slots cannot leak in. A row
    with context_len == 0 returns all zeros (softmax over an all-masked
    row would otherwise go uniform and average the garbage), matching
    the Pallas kernel's empty-stream output. This lowering gathers every
    table slot, (B, nb·bs, H, D) in float32, and masks; the kernel reads
    ``ceil(context_len / bs)`` blocks a stream and nothing of the slots
    past them, so their table entries need only be valid block ids here.
    """
    return paged_attention_multi_reference(
        q[:, None], k_pages, v_pages, block_tables, context_lens[:, None],
        sm_scale=sm_scale, layer=layer)[:, 0]


def _paged_pallas(q, k_pages, v_pages, block_tables, context_lens, sm_scale,
                  layer=None, interpret=False):
    """The decode kernel: :func:`_paged_pallas_multi` with one query lane."""
    return _paged_pallas_multi(q[:, None], k_pages, v_pages, block_tables,
                               context_lens[:, None], sm_scale, layer=layer,
                               interpret=interpret)[:, 0]


def _paged_shapes_ok(q, k_pages):
    # Mosaic pads sublanes/lanes of the trailing (G, W) tile; keep D
    # lane-aligned. bs and nb are free (ragged tails are masked in-kernel).
    return q.shape[-1] % 8 == 0 and q.shape[-1] >= 8


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    sm_scale=None, layer=None):
    """Paged ragged decode attention over a shared KV block pool.

    Platform selected at LOWERING time (like :func:`flash_attention`): the
    Pallas kernel on TPU, the pure-XLA gather reference everywhere else —
    identical outputs, so a CPU CI run proves the math the TPU kernel runs.
    Serving-only (no vjp): the decode path never differentiates.
    """
    return paged_attention_multi(q[:, None], k_pages, v_pages, block_tables,
                                 context_lens[:, None], sm_scale=sm_scale,
                                 layer=layer)[:, 0]


# --------------------------------------------- paged multi-query (verify)
def paged_attention_multi_reference(q, k_pages, v_pages, block_tables,
                                    context_lens, sm_scale=None, layer=None,
                                    window=None, head_major=False, sink=None,
                                    name=None):
    """Pure-XLA multi-query paged attention — q-length > 1 per sequence
    with PER-LANE context lengths. The speculative-decoding verify pass
    and the CPU/CI lowering of the Pallas kernel below.

    Each sequence carries T query lanes (this step's speculative window);
    lane t's K/V has already been scattered into the pool at its position,
    so causality within the window reduces to per-lane masking: lane t may
    only read pool positions < context_lens[b, t].

    q:            (B, T, H, D)     — T query tokens per stream
    k_pages:      (N, bs, G, W)    — the shared K pool, or
                                     (L, N, bs, G, W) with ``layer``
    v_pages:      the shared V pool, same shape
    block_tables: (B, nb) int32    — ONE table per sequence (lanes share it)
    context_lens: (B, T) int32     — valid pool positions PER LANE
                                     (monotone over t for a causal window)

    window:       a lane reads only its last ``window`` positions, those
                  ``>= context_len - window`` (table slots wholly behind
                  every lane's window may name any block)
    head_major:   pages are ``(.., N, G, bs, W)``; ``v_pages`` may then
                  be narrower than ``k_pages`` (the result is as wide as
                  they), and ``context_lens`` (B, 1) is every lane's
    sink:         (T, H) float32, a scalar a lane and head that joins the
                  softmax's denominator and no numerator (here: one more
                  column of the scores, dropped after the softmax)
    name:         the Pallas call's name on a trace (nothing here)

    Returns (B, T, H, D) in q.dtype. T == 1 with context_lens (B, 1)
    is :func:`paged_attention_reference`. A lane with context_len == 0
    returns all zeros, like the single-query oracle.
    """
    sm_scale = _scale(sm_scale, q.shape[-1])
    _heads_per_row(q, k_pages, head_major)
    k_pages, v_pages, layer = _whole_pool(k_pages, v_pages, layer)
    h, d = q.shape[-2:]
    k = _gather_tokens(k_pages[layer], block_tables, h, d,
                       head_major)                           # (B, K, H, D)
    v = _gather_tokens(v_pages[layer], block_tables, h, d, head_major)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
                   precision=lax.Precision.HIGHEST) * sm_scale
    pos = jnp.arange(k.shape[1])[None, None, :]
    valid = pos < context_lens[:, :, None]                   # (B, T, K)
    if window is not None:
        valid = valid & (pos >= context_lens[:, :, None] - window)
    s = jnp.where(valid[:, None, :, :], s, _NEG_INF)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        col = jnp.broadcast_to(sink.astype(jnp.float32).T[None, :, :, None],
                               s.shape[:3] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, col], -1), axis=-1)[..., :-1]
    # all-masked lanes (context_len == 0) softmax to uniform and would
    # average gathered garbage — pin them to zero
    p = jnp.where((context_lens > 0)[:, None, :, None], p, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     precision=lax.Precision.HIGHEST)
    return out.astype(q.dtype)


def _head_sums(x, r):
    """x (bs, G, W) -> (bs, G, W): every lane holds the sum over its own
    head's D = W // r lanes. One masked lane reduction per head of the row;
    r = 1 is the plain sum over the row."""
    if r == 1:
        return jnp.broadcast_to(jnp.sum(x, axis=-1, keepdims=True), x.shape)
    d = x.shape[-1] // r
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    out = None
    for i in range(r):
        mine = (lane >= i * d) & (lane < (i + 1) * d)
        part = jnp.sum(jnp.where(mine, x, 0.0), axis=-1, keepdims=True)
        out = jnp.where(mine, part, 0.0 if out is None else out)
    return out


# One fetch moves this much of K (and as much of V) into VMEM, counted as the
# pages lie there. Two fetches are in flight or in use at a time.
_PAGED_FETCH_BYTES = 512 * 1024


def _paged_page_bytes(bs, g, w, dtype):
    """Bytes of one page ``(bs, G, W)`` in VMEM: ``bs`` row sets ``(G, W)``,
    each padded to the dtype's (sublane, 128) tile."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    return bs * -(-g // sublanes) * sublanes * -(-w // 128) * 128 * itemsize


def _paged_blocks_per_fetch(bs, g, w, dtype, nb):
    """c: how many blocks of K (and of V) one fetch of the paged kernel
    moves, from the page it is handed: as many as fill
    ``_PAGED_FETCH_BYTES`` (8 at GPT-2 medium's 64 KB fp32 pages, 2 at
    OLMoE's 256 KB bf16 pages), at least one and never more than the
    table holds. The scratch is ``4 c`` pages."""
    page = _paged_page_bytes(bs, g, w, dtype)
    return int(max(1, min(nb, _PAGED_FETCH_BYTES // page)))


def _paged_pallas_multi(q, k_pages, v_pages, block_tables, context_lens,
                        sm_scale, layer=None, interpret=False, window=None,
                        head_major=False, sink=None, name=None):
    """Pallas TPU ragged-paged-attention kernel, T query lanes per sequence
    (T = 1 is the decode step, T = k + 1 the speculative verify pass).

    Grid ``(B,)``: one step a stream. The pool stays in HBM where it lies
    (``memory_space=pl.ANY``, the whole 5-D pool with a static ``layer``);
    the block TABLE and the context lengths ride in as scalar-prefetch
    args (SMEM). A stream's step walks its LIVE blocks only:
    ``ceil(max_t context_lens[i, t] / bs)`` of them (one for an empty
    stream), read on the device, in fetches of ``c`` blocks
    (:func:`_paged_blocks_per_fetch`: c follows from the page's bytes).
    A fetch is one async copy per block of K and of V, block ids from the
    table, into one of two slots of a VMEM scratch ``(2, c, bs, G, W)``;
    the next fetch is started before the current one is waited for, and
    the LAST fetch of stream i starts the first of stream i + 1 (scratch,
    semaphores and the slot's parity live across grid steps), so only the
    first stream of a call waits for a whole DMA. A table slot past the
    stream's context costs nothing: no grid step, no copy, no compute.

    Each block takes the per-block arithmetic as it arrives (its own
    semaphore): online-softmax state (m, l, acc) in VMEM scratch, float32
    scores and accumulators; bf16 pages are read as bf16 and widened on
    the chip. Masking is per lane (``context_lens`` is (B, T)): the loop is
    bounded by the LONGEST lane and shorter lanes mask the tail with -1e30.
    The page's LAYOUT decides the unit the block's two products run on:

    * token-major pages ``(bs, G, W)``, r heads a row — the VPU: the lanes
      take the block one after another (T is static and small), one
      (G, W) row set of state a lane; a head's score is summed over its own
      D lanes and kept broadcast across them (:func:`_head_sums`), so
      every line after it is elementwise on full (8, 128) registers
      whatever r is. An MXU form would need a relayout a block.
    * ``head_major`` pages ``(G, bs, W)``, one head a row — the MXU: a
      row's T query lanes ride side by side (``q`` as ``(G, Tp, W)``, T
      padded to eight sublanes) against the row's ``(bs, W)`` slab, which
      is whole tiles as it lies: ``S = einsum("gtw,gsw->gts")``, ONE
      softmax over ``(G, Tp, bs)`` (a score is computed, masked and
      exponentiated once, not once a lane of its row), then
      ``einsum("gts,gsw->gtw", P, V)`` with P in float32: on bf16 pages
      its three bf16 parts (24 bits between them) ride one matmul against
      the V tile, which is exact in bf16, and meet again in the float32
      accumulator; a bf16 ``q`` meets bf16 pages as it is (the product of
      two bf16 values is exact in float32), any other pair of types in
      float32 at full precision. State ``(G, Tp, 1)`` and ``(G, Tp, W)``.
      Four lanes on ten rows of 128: 0.50–0.54 us a 320 KB block where the
      lane-by-lane sweeps took 0.93–0.98 (PERF.md section 6, PR 41).

    VMEM: ``4 c`` pages (2 MB at the default) plus O(T·G·W), independent
    of sequence length, table width and pool size.

    An async copy cuts HBM between whole (8, 128) tiles, so token-major
    page rows with ``G % 8`` or ``W % 128`` left over are padded at the
    edge — a copy of the layer's pages a call; ``(8, 128)`` and
    ``(16, 128)`` rows (both served configurations of the benchmark) are
    read where they lie. So are ``head_major`` pages of any G (ten rows of
    128): a row's ``(bs, W)`` slab is whole tiles.

    ``window``: a stream's walk starts at the block that holds position
    ``min_t context_lens[i, t] - window`` instead of at its first, and a
    lane masks what lies before its own ``context_len - window``: table
    slots behind the window are never read and may name any block.

    Head-major pages also take (plain grouped-query attention's calls):
    ``v_pages`` NARROWER than ``k_pages`` (keys of 192 lanes in a 256-lane
    row beside values of 128: the second matmul, the accumulator and the
    result are as wide as V's rows); ``context_lens`` (B, 1) for T lanes
    that share it (a K/V row's query heads: one SMEM read, one select);
    ``sink`` (T, G) float32, the online softmax's START STATE — m0 the
    sink, l0 = 1, acc0 = 0: it joins the denominator and no numerator, and
    the score needs no column for it; ``name``, the custom call's name on a
    trace (without one it is ``branch_0_fun``, as every earlier call's).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = _heads_per_row(q, k_pages, head_major)
    k_pages, v_pages, layer = _whole_pool(k_pages, v_pages, layer)
    b, tq, h, d = q.shape
    out_dtype = q.dtype
    nb = block_tables.shape[1]
    if head_major:
        g, bs, w = k_pages.shape[2:]
        sublanes = 32 // jnp.dtype(k_pages.dtype).itemsize
        if w % 128 or bs % sublanes:
            raise ValueError("head-major pages need whole (%d, 128) tiles a "
                             "row, not (%d, %d)" % (sublanes, bs, w))
        # bf16 queries on bf16 pages meet on the MXU as they are (a product
        # of two bf16 values is exact in float32); anything else in float32
        mxu = (jnp.bfloat16 if q.dtype == k_pages.dtype == jnp.bfloat16
               else jnp.float32)
        prec = None if mxu == jnp.bfloat16 else jax.lax.Precision.HIGHEST
        # a row's query lanes side by side, padded to eight sublanes
        tp = -(-tq // 8) * 8
        q = jnp.pad(q.transpose(0, 2, 1, 3).astype(mxu),
                    ((0, 0), (0, 0), (0, tp - tq), (0, 0)))  # (B, G, Tp, W)
        # a row's slab is whole tiles: the page's bytes as they are
        c = _paged_blocks_per_fetch(bs * g // sublanes, sublanes, w,
                                    k_pages.dtype, nb)
        wv = v_pages.shape[-1]
        page, state, stat = (g, bs, w), (g, tp, w), (g, tp, 1)
        v_page, o_state = (g, bs, wv), (g, tp, wv)
        if sink is not None:
            sink = jnp.pad(sink.astype(jnp.float32).T,
                           ((0, 0), (0, tp - tq)))[..., None]    # (G, Tp, 1)
    else:
        if sink is not None or v_pages.shape[-1] != k_pages.shape[-1]:
            raise ValueError("a sink and V pages narrower than K pages "
                             "need head-major pages")
        rows = k_pages.shape[3:]
        q = q.reshape((b, tq) + rows)
        # rows that do not fill their tiles ((6, 128), (12, 64)): see above
        g, w = -(-rows[0] // 8) * 8, -(-rows[1] // 128) * 128
        if (g, w) != rows:
            def pad(x):
                return jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                               + [(0, g - rows[0]), (0, w - rows[1])])
            q, k_pages, v_pages, layer = (pad(q), pad(k_pages[layer][None]),
                                          pad(v_pages[layer][None]), 0)
        bs = k_pages.shape[2]
        c = _paged_blocks_per_fetch(bs, g, w, k_pages.dtype, nb)
        page, state = (bs, g, w), (tq, g, w)
        stat, v_page, o_state = state, page, state
    # T lanes of one context length (B, 1): lane 0's is every lane's
    shared = context_lens.shape[1] == 1 and tq > 1
    ncl = 1 if shared else tq

    def kernel(bt_ref, cl_ref, q_ref, *refs):
        if sink is not None:
            sink_ref, *refs = refs
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref, m_ref, l_ref,
         acc_ref) = refs
        i = pl.program_id(0)  # sequence

        def first_block(seq):
            """The table slot a stream's walk starts at: the block of the
            shortest lane's first visible position."""
            shortest = functools.reduce(
                jnp.minimum, [cl_ref[seq, t] for t in range(ncl)])
            return jnp.maximum(shortest - window, 0) // bs

        def live_blocks(seq):
            # SMEM yields scalars only: one read per lane, T is static
            longest = functools.reduce(
                jnp.maximum, [cl_ref[seq, t] for t in range(ncl)])
            # one block for an empty stream; never past the table
            n = jnp.clip(pl.cdiv(longest, bs), 1, nb)
            return n if window is None else n - first_block(seq)

        def copies(seq, blk, slot, j):
            """The two async copies (K, V) of the ``blk``-th block of
            stream ``seq``'s walk into place ``j`` of ``slot``; one
            semaphore a place."""
            if window is not None:
                blk = blk + first_block(seq)
            page = bt_ref[seq, blk]
            return [pltpu.make_async_copy(hbm.at[layer, page],
                                          buf.at[slot, j], sems.at[slot, j])
                    for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf))]

        def start_fetch(seq, it, slot, n_blk):
            def start(j, _):
                for cp in copies(seq, it * c + j, slot, j):
                    cp.start()
                return 0

            jax.lax.fori_loop(0, jnp.minimum(c, n_blk - it * c), start, 0)

        @pl.when(i == 0)
        def _first():
            slot_ref[0] = 0
            start_fetch(0, 0, 0, live_blocks(0))

        if sink is None:
            m_ref[:] = jnp.full(stat, _NEG_INF, jnp.float32)
            l_ref[:] = jnp.zeros(stat, jnp.float32)
        else:           # the sink is the softmax's start state
            m_ref[:] = sink_ref[...]
            l_ref[:] = jnp.ones(stat, jnp.float32)
        acc_ref[:] = jnp.zeros(o_state, jnp.float32)
        ctx = [cl_ref[i, t] for t in range(ncl)]
        n_blk = live_blocks(i)
        n_fetch = pl.cdiv(n_blk, c)
        slot0 = slot_ref[0]
        nxt = jnp.minimum(i + 1, b - 1)
        nxt_blk = live_blocks(nxt)

        def lanes_ctx(shape):
            """The lanes' context lengths down axis 1 of ``shape``, 0 (an
            empty lane) on the rows that pad T to a tile."""
            lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            if shared:
                return jnp.where(lane < tq, ctx[0], 0)
            return functools.reduce(
                lambda x, t: jnp.where(lane == t, ctx[t], x), range(tq),
                jnp.zeros(shape, jnp.int32))

        def mxu_block(first, k, v):
            """A head-major block ``(G, bs, W)`` whose first slot holds
            position ``first``: every row's T lanes against its slab in two
            batched matmuls around ONE softmax over ``(G, Tp, bs)``."""
            s = jax.lax.dot_general(
                q_ref[0], k.astype(mxu), (((2,), (2,)), ((0,), (0,))),
                precision=prec,
                preferred_element_type=jnp.float32) * sm_scale
            pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            upto = lanes_ctx(s.shape)
            seen = pos < upto
            if window is not None:
                seen = seen & (pos >= upto - window)
            s = jnp.where(seen, s, _NEG_INF)
            m = m_ref[:]                                     # (G, Tp, 1)
            m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
            p = jnp.exp(s - m_new)
            scale = jnp.exp(m - m_new)
            m_ref[:] = m_new
            l_ref[:] = l_ref[:] * scale + jnp.sum(p, axis=2, keepdims=True)
            pv_dims = (((2,), (1,)), ((0,), (0,)))
            if mxu == jnp.float32:
                pv = jax.lax.dot_general(
                    p, v.astype(mxu), pv_dims, precision=prec,
                    preferred_element_type=jnp.float32)
            else:
                # P stays float32: its three bf16 parts (24 bits between
                # them) ride one matmul against the V tile, which is exact
                # in bf16, and meet again in the float32 accumulator
                parts, rest = [], p
                for _ in range(3):
                    parts.append(rest.astype(mxu))
                    rest = rest - parts[-1].astype(jnp.float32)
                pv = jax.lax.dot_general(
                    jnp.concatenate(parts, axis=1), v, pv_dims,
                    preferred_element_type=jnp.float32)
                pv = pv[:, :tp] + pv[:, tp:2 * tp] + pv[:, 2 * tp:]
            acc_ref[:] = acc_ref[:] * scale + pv

        def vpu_block(first, k, v):
            """A token-major block ``(bs, G, W)``, r heads a row: the lanes
            one after another, elementwise on whole registers."""
            kv = k.astype(jnp.float32)
            vv = v.astype(jnp.float32)
            pos = first + jax.lax.broadcasted_iota(jnp.int32, page, 0)
            for t in range(tq):
                qv = q_ref[0, t].astype(jnp.float32)             # (G, W)
                s = _head_sums(qv[None] * kv, r) * sm_scale      # (bs, G, W)
                if window is None:
                    s = jnp.where(pos < ctx[t], s, _NEG_INF)
                else:
                    s = jnp.where((pos < ctx[t])
                                  & (pos >= ctx[t] - window), s, _NEG_INF)
                m = m_ref[t]
                m_new = jnp.maximum(m, jnp.max(s, axis=0))
                p = jnp.exp(s - m_new[None])
                scale = jnp.exp(m - m_new)
                m_ref[t] = m_new
                l_ref[t] = l_ref[t] * scale + jnp.sum(p, axis=0)
                acc_ref[t] = acc_ref[t] * scale + jnp.sum(p * vv, axis=0)

        block_math = mxu_block if head_major else vpu_block

        def fetch_step(it, _):
            slot = (slot0 + it) % 2

            # the next fetch into the other slot before this one is waited
            # for: this stream's, or after its last the next stream's first
            last = it + 1 == n_fetch

            @pl.when(jnp.logical_not(last) | (i + 1 < b))
            def _prefetch():
                start_fetch(jnp.where(last, nxt, i),
                            jnp.where(last, 0, it + 1), 1 - slot,
                            jnp.where(last, nxt_blk, n_blk))

            def block_step(j, _):
                blk = it * c + j
                for cp in copies(i, blk, slot, j):
                    cp.wait()
                if window is not None:
                    blk = blk + first_block(i)
                block_math(blk * bs, k_buf[slot, j], v_buf[slot, j])
                return 0

            jax.lax.fori_loop(0, jnp.minimum(c, n_blk - it * c), block_step, 0)
            return 0

        jax.lax.fori_loop(0, n_fetch, fetch_step, 0)
        slot_ref[0] = (slot0 + n_fetch) % 2
        # a lane that never saw a valid position accumulated
        # exp(-1e30 - -1e30) = 1 weights over garbage — pin it to the
        # oracle's empty-lane zero
        if head_major:
            out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
            o_ref[0] = jnp.where(lanes_ctx(o_state) > 0, out,
                                 0.0).astype(o_ref.dtype)
            return
        for t in range(tq):
            out = acc_ref[t] / jnp.maximum(l_ref[t], 1e-30)
            out = jnp.where(ctx[t] > 0, out, 0.0)
            o_ref[0, t] = out.astype(o_ref.dtype)

    def lane_spec(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda i, bt, cl: (i,) + (0,) * len(shape))

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    sink_arg = [] if sink is None else [sink]
    sink_spec = [pl.BlockSpec(stat, lambda i, bt, cl: (0, 0, 0))
                 for _ in sink_arg]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[lane_spec(state)] + sink_spec + [pool_spec, pool_spec],
        out_specs=lane_spec(o_state),
        scratch_shapes=[pltpu.VMEM((2, c) + page, k_pages.dtype),
                        pltpu.VMEM((2, c) + v_page, v_pages.dtype),
                        pltpu.SemaphoreType.DMA((2, c)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM(stat, jnp.float32),
                        pltpu.VMEM(stat, jnp.float32),
                        pltpu.VMEM(o_state, jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b,) + o_state, out_dtype),
        # the scratch carries one stream's prefetch into the next step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        **({} if name is None else {"name": name}),
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      q, *sink_arg, k_pages, v_pages)
    if head_major:
        return out[:, :, :tq].transpose(0, 2, 1, 3).reshape(b, tq, h, wv)
    return out[:, :, :rows[0], :rows[1]].reshape(b, tq, h, d)


def paged_attention_multi(q, k_pages, v_pages, block_tables, context_lens,
                          sm_scale=None, layer=None, window=None,
                          head_major=False, sink=None, name=None):
    """Multi-query paged attention over a shared KV block pool: q is
    (B, T, H, D), context_lens (B, T) per lane — the speculative-decoding
    verify pass scores all T = k+1 window positions in this ONE dispatch.

    Platform selected at LOWERING time like :func:`flash_attention`: the
    Pallas kernel on TPU, the pure-XLA gather reference everywhere else.
    Serving-only (no vjp). ``window``, ``head_major``, ``sink`` and
    ``name``: see :func:`paged_attention_multi_reference`.
    """
    sm_scale = _scale(sm_scale, q.shape[-1])
    kw = {"sm_scale": sm_scale, "layer": layer}
    if window is not None or head_major:    # the existing calls' jaxprs stay
        kw.update(window=window, head_major=head_major)
    if sink is not None or name is not None:
        kw.update(sink=sink, name=name)
    if _paged_shapes_ok(q, k_pages):
        return lax.platform_dependent(
            q, k_pages, v_pages, block_tables, context_lens,
            tpu=functools.partial(_paged_pallas_multi, **kw),
            default=functools.partial(paged_attention_multi_reference, **kw),
        )
    return paged_attention_multi_reference(q, k_pages, v_pages, block_tables,
                                           context_lens, **kw)


# ---------------------------------------------------------------------------
# latent attention's decode step (DeepSeek-V3's MLA, absorbed form)
# ---------------------------------------------------------------------------
def _latent_layer(c_pages, r_pages, layer):
    """The latent pool is 5-D ``(L, N, 1, bs, W)`` with a static ``layer``,
    or one layer's 4-D pages."""
    if c_pages.ndim == 4:
        return c_pages[None], r_pages[None], 0
    return c_pages, r_pages, int(layer)


def latent_paged_reference(qc, qr, c_pages, r_pages, block_tables,
                           context_lens, sm_scale, layer=None):
    """The XLA lowering and the oracle of :func:`latent_paged`: gather each
    stream's blocks, then plain masked attention of the H query rows over
    the one cached row a token. float32 softmax."""
    c_pages, r_pages, layer = _latent_layer(c_pages, r_pages, layer)
    b, nb = block_tables.shape
    bs = c_pages.shape[3]
    prec = fp32_precision(qc.dtype)

    def rows(pages):
        t = jnp.take(pages[layer], block_tables, axis=0)   # (B, nb, 1, bs, W)
        return t.reshape(b, nb * bs, t.shape[-1])

    c, r = rows(c_pages), rows(r_pages)
    s = (jnp.einsum("bhc,btc->bht", qc, c, precision=prec,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,btr->bht", qr, r, precision=prec,
                      preferred_element_type=jnp.float32)) * sm_scale
    seen = jnp.arange(nb * bs)[None, None] < context_lens[:, None, None]
    s = jnp.where(seen, s, _NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen, p, 0.0)
    out = jnp.einsum("bht,btc->bhc", p.astype(c.dtype), c, precision=prec,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return out.astype(qc.dtype)


#: cached rows one fetch of the latent kernel moves and ONE pass of its
#: arithmetic takes. A pass costs about a microsecond whatever it covers (two
#: matmuls, two lane reductions and the state's update, each waiting for the
#: last): at a block a pass the kernel ran at 9-32% of the HBM peak, the
#: larger the block the better (PERF.md section 6, PR 33); the rows past a
#: stream's last block are computed and masked
_LATENT_FETCH_ROWS = 512


def _latent_pallas(qc, qr, c_pages, r_pages, block_tables, context_lens,
                   sm_scale, layer=None, interpret=False):
    """Pallas TPU kernel of :func:`latent_paged`; the custom call is named
    ``latent_paged``.

    Grid ``(B,)``, one step a stream, and :func:`_paged_pallas_multi`'s
    walk: the pools stay in HBM, the block table and the context lengths
    ride in SMEM, a stream's LIVE blocks only are fetched, ``c`` blocks a
    fetch into one of two VMEM slots, the next fetch (this stream's, or
    the next stream's first) started before the current one is waited
    for. What differs is the arithmetic: all H query heads read the SAME
    cached row, so a FETCH (its ``c`` blocks side by side, ``c x bs`` rows)
    is two matmuls on the MXU in the pages' type with float32 accumulation
    — scores ``q_c (H, C) . c^T + q_r (H, R) . r^T`` -> ``(H, c bs)``, then
    ``P (H, c bs) . c (c bs, C)``: the value is the latent itself — around
    an online softmax whose state is ``(H,)`` and ``(H, C)`` float32. The
    places of a fetch that a stream's last blocks do not fill keep an
    earlier fetch's rows (zeros before the first): finite, and masked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c_pages, r_pages, layer = _latent_layer(c_pages, r_pages, layer)
    b, h, wc = qc.shape
    wr = qr.shape[2]
    nb = block_tables.shape[1]
    bs = c_pages.shape[3]
    c = int(max(1, min(nb, _LATENT_FETCH_ROWS // bs)))
    rows = c * bs
    nt = (((1,), (1,)), ((), ()))       # contract both on their lanes

    def kernel(bt_ref, cl_ref, qc_ref, qr_ref, c_hbm, r_hbm, o_ref,
               c_buf, r_buf, sems, slot_ref, m_ref, l_ref, acc_ref):
        i = pl.program_id(0)

        def live_blocks(seq):
            # one block for an empty stream; never past the table
            return jnp.clip(pl.cdiv(cl_ref[seq], bs), 1, nb)

        def copies(seq, blk, slot, j):
            page = bt_ref[seq, blk]
            return [pltpu.make_async_copy(hbm.at[layer, page, 0],
                                          buf.at[slot, j], sems.at[n, slot, j])
                    for n, (hbm, buf) in enumerate(((c_hbm, c_buf),
                                                    (r_hbm, r_buf)))]

        def start_fetch(seq, it, slot, n_blk):
            def start(j, _):
                for cp in copies(seq, it * c + j, slot, j):
                    cp.start()
                return 0

            jax.lax.fori_loop(0, jnp.minimum(c, n_blk - it * c), start, 0)

        @pl.when(i == 0)
        def _first():
            slot_ref[0] = 0
            c_buf[...] = jnp.zeros(c_buf.shape, c_buf.dtype)
            r_buf[...] = jnp.zeros(r_buf.shape, r_buf.dtype)
            start_fetch(0, 0, 0, live_blocks(0))

        m_ref[:] = jnp.full((h,), _NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros((h,), jnp.float32)
        acc_ref[:] = jnp.zeros((h, wc), jnp.float32)
        ctx = cl_ref[i]
        n_blk = live_blocks(i)
        n_fetch = pl.cdiv(n_blk, c)
        slot0 = slot_ref[0]
        nxt = jnp.minimum(i + 1, b - 1)
        nxt_blk = live_blocks(nxt)

        def fetch_step(it, _):
            slot = (slot0 + it) % 2
            last = it + 1 == n_fetch

            @pl.when(jnp.logical_not(last) | (i + 1 < b))
            def _prefetch():
                start_fetch(jnp.where(last, nxt, i),
                            jnp.where(last, 0, it + 1), 1 - slot,
                            jnp.where(last, nxt_blk, n_blk))

            def wait(j, _):
                for cp in copies(i, it * c + j, slot, j):
                    cp.wait()
                return 0

            jax.lax.fori_loop(0, jnp.minimum(c, n_blk - it * c), wait, 0)
            cv = c_buf[slot].reshape(rows, wc)
            rv = r_buf[slot].reshape(rows, wr)
            s = (jax.lax.dot_general(
                qc_ref[0], cv, nt, preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    qr_ref[0], rv, nt, preferred_element_type=jnp.float32)
            ) * sm_scale                                 # (H, c bs)
            pos = it * rows + jax.lax.broadcasted_iota(
                jnp.int32, (h, rows), 1)
            s = jnp.where(pos < ctx, s, _NEG_INF)
            m = m_ref[:]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            scale = jnp.exp(m - m_new)
            m_ref[:] = m_new
            l_ref[:] = l_ref[:] * scale + jnp.sum(p, axis=-1)
            acc_ref[:] = acc_ref[:] * scale[:, None] + jnp.dot(
                p.astype(cv.dtype), cv, preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, n_fetch, fetch_step, 0)
        slot_ref[0] = (slot0 + n_fetch) % 2
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)[:, None]
        # a stream with no valid position: the oracle's zero
        o_ref[0] = jnp.where(ctx > 0, out, 0.0).astype(o_ref.dtype)

    def q_spec(w):
        return pl.BlockSpec((1, h, w), lambda i, bt, cl: (i, 0, 0))

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[q_spec(wc), q_spec(wr), pool_spec, pool_spec],
        out_specs=q_spec(wc),
        scratch_shapes=[pltpu.VMEM((2, c, bs, wc), c_pages.dtype),
                        pltpu.VMEM((2, c, bs, wr), r_pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 2, c)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((h,), jnp.float32),
                        pltpu.VMEM((h,), jnp.float32),
                        pltpu.VMEM((h, wc), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, wc), qc.dtype),
        # the scratch carries one stream's prefetch into the next step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged",
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      qc, qr, c_pages, r_pages)


def _latent_shapes_ok(qc, qr, c_pages):
    """Whole tiles for the MXU and the copies: full lanes, a block of whole
    sublane tiles, head-major pages of one row a token."""
    sublanes = 32 // jnp.dtype(c_pages.dtype).itemsize
    return (qc.shape[-1] % 128 == 0 and qr.shape[-1] % 128 == 0
            and qc.shape[1] % 8 == 0 and c_pages.shape[-3] == 1
            and c_pages.shape[-2] % max(sublanes, 8) == 0)


def latent_paged(qc, qr, c_pages, r_pages, block_tables, context_lens,
                 sm_scale, layer=None):
    """One decode step of multi-head LATENT attention (MLA), absorbed: all
    H query heads of a stream read the same cached row a token.

    qc:  (B, H, C) — a head's query through ``W_uk``: against the latent
    qr:  (B, H, R) — its rotary part, zero past the rotary lanes
    c_pages / r_pages: the pool's ``k_pages`` / ``v_pages`` in the latent
         format, ``(L, N, 1, bs, C)`` / ``(L, N, 1, bs, R)`` with a static
         ``layer`` (or one layer's 4-D pages): the normalised latent and
         the rotated key, R its 128-lane row
    block_tables (B, nb), context_lens (B,)

    Returns ``softmax((qc . c + qr . r) * sm_scale) c``: (B, H, C), to go
    through ``W_uv``. The Pallas kernel on the TPU (``latent_paged`` on a
    trace), the gather reference elsewhere; serving-only (no vjp)."""
    kw = {"sm_scale": float(sm_scale), "layer": layer}
    if _latent_shapes_ok(qc, qr, c_pages):
        return lax.platform_dependent(
            qc, qr, c_pages, r_pages, block_tables, context_lens,
            tpu=functools.partial(_latent_pallas, **kw),
            default=functools.partial(latent_paged_reference, **kw))
    return latent_paged_reference(qc, qr, c_pages, r_pages, block_tables,
                                  context_lens, **kw)


@register(
    "_contrib_PagedAttention",
    arg_names=("query", "key_pages", "value_pages", "block_table",
               "context_len"),
    params={
        "sm_scale": Param.float(-1.0),
    },
)
def _paged_attention_op(octx, attrs, args, auxs):
    """Paged decode attention (serving): one query token per sequence over a
    block-paged shared KV pool. query: (B, heads, head_dim); key_pages/
    value_pages: (num_blocks, block_size, heads, head_dim); block_table:
    (B, nb); context_len: (B,). The serving engine drives the jax-level
    :func:`paged_attention` directly; this registration keeps the kernel
    reachable from nd/sym like every other op."""
    q, kp, vp, bt, cl = args
    scale = attrs["sm_scale"]
    out = paged_attention(q, kp, vp, bt.astype(jnp.int32),
                          cl.astype(jnp.int32),
                          None if scale <= 0 else scale)
    return [out], []


def _paged_infer_shape(attrs, in_shapes, aux_shapes):
    qs = in_shapes[0]
    if qs is None:
        raise ValueError("PagedAttention: query shape required")
    return in_shapes, [tuple(qs)], []


get_op("_contrib_PagedAttention")._infer_shape = _paged_infer_shape
