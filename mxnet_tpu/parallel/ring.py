"""Ring attention + Ulysses sequence parallelism over the ICI mesh.

The reference has **no** sequence/context parallelism (SURVEY §5 long-context:
bucketing + fused RNNs only) — this is green-field TPU design. Two schemes:

* ``ring_attention``: q/k/v sharded over a mesh axis along the sequence.
  Each device keeps its Q shard resident and rotates K/V shards around the
  ring with ``lax.ppermute`` (one ICI hop per step, comm overlapped with the
  block matmuls by XLA), maintaining FlashAttention online-softmax state
  (m, l, acc). Memory per device is O(S/n); the full S×S score matrix never
  exists. Backward re-rotates K/V and carries dk/dv accumulators *with* their
  blocks so each lands home after a full circle — the flash backward
  recurrence distributed over the ring (custom_vjp; only (q,k,v,out,lse)
  local shards are saved).
* ``ulysses_attention``: all-to-all resharding — swap sequence sharding for
  head sharding (``lax.all_to_all``), run dense local flash attention over
  the full sequence, swap back. Cheaper comm for moderate S when
  heads % n == 0.

Both are built on the same ``_block_update`` kernel as ops/attention.py, so the
single-chip and sequence-parallel paths share numerics exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.attention import _NEG_INF, _block_update, _scale, flash_attention
from ..ops.registry import fp32_precision

__all__ = ["ring_attention", "ulysses_attention", "ring_attention_local", "ulysses_attention_local"]


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ------------------------------------------------------------------- ring core
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention_local(q, k, v, axis, n, causal=False, sm_scale=None):
    """Per-device body: q/k/v are local shards (B, H, S/n, D), inside shard_map."""
    out, _ = _ring_fwd_impl(q, k, v, axis, n, causal, sm_scale)
    return out


def _ring_fwd_impl(q, k, v, axis, n, causal, sm_scale):
    b, h, s_loc, d = q.shape
    scale = _scale(sm_scale, d)
    idx = lax.axis_index(axis)
    perm = [(j, (j + 1) % n) for j in range(n)]
    prec = fp32_precision(q.dtype)
    qf = q.astype(jnp.float32)
    q_pos = idx * s_loc + jnp.arange(s_loc)

    def step(carry, i):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - i) % n  # home rank of the block currently held
        k_pos = src * s_loc + jnp.arange(s_loc)
        mask = (q_pos[:, None] >= k_pos[None, :]) if causal else None
        m, l, acc = _block_update(
            qf, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32), m, l, acc, scale, mask,
            precision=prec,
        )
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (k_blk, v_blk, m, l, acc), None

    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (k_out, v_out, m, l, acc), _ = lax.scan(step, (k, v, m0, l0, acc0), jnp.arange(n))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)
    return out, lse


def _ring_fwd(q, k, v, axis, n, causal, sm_scale):
    out, lse = _ring_fwd_impl(q, k, v, axis, n, causal, sm_scale)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis, n, causal, sm_scale, res, g):
    q, k, v, out, lse = res
    b, h, s_loc, d = q.shape
    scale = _scale(sm_scale, d)
    idx = lax.axis_index(axis)
    perm = [(j, (j + 1) % n) for j in range(n)]
    prec = fp32_precision(q.dtype)
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(out.astype(jnp.float32) * gf, axis=-1)  # (B,H,S_loc)
    q_pos = idx * s_loc + jnp.arange(s_loc)

    def step(carry, i):
        k_blk, v_blk, dk_acc, dv_acc, dq = carry
        src = (idx - i) % n
        k_pos = src * s_loc + jnp.arange(s_loc)
        kf = k_blk.astype(jnp.float32)
        vf = v_blk.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf, preferred_element_type=jnp.float32,
                       precision=prec) * scale
        if causal:
            s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p, gf, preferred_element_type=jnp.float32,
                                     precision=prec)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf, preferred_element_type=jnp.float32,
                        precision=prec)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kf, preferred_element_type=jnp.float32,
                             precision=prec)
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, qf, preferred_element_type=jnp.float32,
                                     precision=prec)
        # rotate the block AND its gradient accumulator together: after a full
        # circle both are back on the block's home device
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        dk_acc = lax.ppermute(dk_acc, axis, perm)
        dv_acc = lax.ppermute(dv_acc, axis, perm)
        return (k_blk, v_blk, dk_acc, dv_acc, dq), None

    z = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (_, _, dk, dv, dq), _ = lax.scan(step, (k, v, z, z, z), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention_local.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q, k, v, mesh, axis="sp", causal=False, sm_scale=None):
    """Sequence-parallel attention over global (B, H, S, D) arrays.

    Shards the sequence dim over ``mesh`` axis ``axis`` and runs the ring.
    S must be divisible by the axis size.
    """
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError("seq len %d not divisible by %s=%d" % (q.shape[2], axis, n))
    spec = P(None, None, axis, None)
    fn = _shard_map(
        functools.partial(ring_attention_local, axis=axis, n=n, causal=causal, sm_scale=sm_scale),
        mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


# -------------------------------------------------------------------- ulysses
def ulysses_attention_local(q, k, v, axis, n, causal=False, sm_scale=None):
    """Per-device body: seq-sharded (B, H, S/n, D) in → all-to-all to
    head-sharded (B, H/n, S, D) → dense flash attention → all-to-all back."""

    def seq_to_heads(t):
        # split heads (axis 1) across devices, gather sequence (axis 2)
        return lax.all_to_all(t, axis, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(t):
        return lax.all_to_all(t, axis, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = flash_attention(qh, kh, vh, causal, sm_scale)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh, axis="sp", causal=False, sm_scale=None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism. Requires
    heads % axis_size == 0 and S % axis_size == 0."""
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError("heads %d not divisible by %s=%d" % (q.shape[1], axis, n))
    if q.shape[2] % n:
        raise ValueError("seq len %d not divisible by %s=%d" % (q.shape[2], axis, n))
    spec = P(None, None, axis, None)
    fn = _shard_map(
        functools.partial(ulysses_attention_local, axis=axis, n=n, causal=causal, sm_scale=sm_scale),
        mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
