"""Selective state-space (Mamba-1) recurrences for serving.

Per channel ``d`` and state ``n``, with ``dt = softplus(dt_raw)``::

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] B_t[n] x_t[d]
    y_t[d]    = sum_n C_t[n] h_t[n, d] + D[d] x_t[d]
    out_t[d]  = y_t[d] * silu(z_t[d])

The state is laid out ``(N, Dn)`` — states along the sublanes, channels along
the lanes — so that a ``(16, 5120)`` float32 state is whole ``(8, 128)`` tiles
(``(5120, 16)`` would pad its 16 lanes to 128: eight times the bytes).

Two entry points, both serving-only (no vjp), platform chosen at LOWERING
time like the attention kernels (``lax.platform_dependent``): a Pallas
kernel on the TPU, plain XLA elsewhere.

* :func:`ssm_scan` — prefill, one stream: sequential over chunks of time
  with the state carried in VMEM, parallel over channel blocks; softplus,
  the recurrence, ``D x`` and the gate are one kernel (the custom call is
  named ``ssm_scan``). Positions at or past ``length`` leave the state as
  it is (their ``dt`` is taken as 0).
* :func:`ssm_step` — decode, one token for each of B streams: each stream's
  state is read from its SLOT of the per-layer state array and written back
  to the same place (``input_output_aliases``; the custom call is named
  ``ssm_step``); nothing but the B slots is touched.

Everything inside is float32: ``dt``, ``A``, the state, the sums. ``y`` and
``out`` come back in ``x``'s type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssm_scan", "ssm_scan_reference", "ssm_step",
           "ssm_step_reference"]

_TIME_CHUNK = 64        # timesteps a grid step of the scan kernel holds
_CHANNEL_BLOCK = 512    # channels a grid step of the scan kernel holds


def _silu(t):
    return t * jax.nn.sigmoid(t)


def _one_step(h, x, dt, a, b, c, d, z):
    """One token of the recurrence, float32. h, a: (.., N, Dn); x, dt, z, d:
    (.., 1, Dn); b, c: (.., N, 1). Returns (h, y, out)."""
    h = jnp.exp(dt * a) * h + (dt * x) * b
    y = jnp.sum(c * h, axis=-2, keepdims=True) + d * x
    return h, y, y * _silu(z)


# ------------------------------------------------------------------ prefill
def ssm_scan_reference(x, dt, a, b, c, d, z, h0, length):
    """The XLA lowering and the oracle of :func:`ssm_scan`: ``lax.scan``
    over time."""
    f32 = jnp.float32
    seq = x.shape[0]
    live = (jnp.arange(seq) < length)[:, None]
    dts = jnp.where(live, jax.nn.softplus(dt.astype(f32)), 0.0)
    a, d = a.astype(f32), d.astype(f32)[None]

    def step(h, xs):
        xt, dtt, bt, ct, zt = xs
        h, y, out = _one_step(h, xt[None], dtt[None], a, bt[:, None],
                              ct[:, None], d, zt[None])
        return h, (y[0], out[0])

    h, (y, out) = lax.scan(step, h0.astype(f32), (
        x.astype(f32), dts, b.astype(f32), c.astype(f32), z.astype(f32)))
    return y.astype(x.dtype), out.astype(x.dtype), h


def _scan_blocks(seq, dn):
    tc = _TIME_CHUNK if seq % _TIME_CHUNK == 0 else 8
    bd = _CHANNEL_BLOCK if dn % _CHANNEL_BLOCK == 0 else 128
    return tc, bd


def _scan_shapes_ok(x, a):
    return (x.shape[0] % 8 == 0 and x.shape[1] % 128 == 0
            and a.shape[0] % 8 == 0)


def _scan_pallas(x, dt, a, b, c, d, z, h0, length, interpret=False):
    """Grid (channel blocks, time chunks), time innermost: the state block
    ``(N, bd)`` lives in the final-state output's VMEM block across a
    channel block's chunks (its index does not move with time) and is
    written to HBM once, after the last chunk. A chunk is taken in groups of
    8 timesteps: one aligned ``(8, bd)`` tile of each input in, eight
    updates unrolled, one aligned tile of ``y`` and of ``out`` back."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    seq, dn = x.shape
    n = a.shape[0]
    tc, bd = _scan_blocks(seq, dn)

    def kernel(len_ref, x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
               h0_ref, y_ref, o_ref, h_ref):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            h_ref[...] = h0_ref[...]

        av, dv, n_live = a_ref[...], d_ref[...], len_ref[0]

        def group(g, h):
            t0 = pl.multiple_of(g * 8, 8)
            rows = pl.ds(t0, 8)
            xs, zs = x_ref[rows, :], z_ref[rows, :]
            t = k * tc + t0 + lax.broadcasted_iota(jnp.int32, (8, bd), 0)
            dts = jnp.where(t < n_live, jax.nn.softplus(dt_ref[rows, :]), 0.0)
            ys, outs = [], []
            for i in range(8):
                h, y, out = _one_step(
                    h, xs[i:i + 1], dts[i:i + 1], av, b_ref[t0 + i],
                    c_ref[t0 + i], dv, zs[i:i + 1])
                ys.append(y)
                outs.append(out)
            y_ref[rows, :] = jnp.concatenate(ys, axis=0)
            o_ref[rows, :] = jnp.concatenate(outs, axis=0)
            return h

        h_ref[...] = lax.fori_loop(0, tc // 8, group, h_ref[...])

    row_spec = pl.BlockSpec((tc, bd), lambda j, k, _len: (k, j))
    col_spec = pl.BlockSpec((tc, n, 1), lambda j, k, _len: (k, 0, 0))
    chan_spec = pl.BlockSpec((n, bd), lambda j, k, _len: (0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(dn // bd, seq // tc),
        in_specs=[row_spec, row_spec, row_spec, col_spec, col_spec,
                  chan_spec,
                  pl.BlockSpec((1, bd), lambda j, k, _len: (0, j)),
                  chan_spec],
        out_specs=[row_spec, row_spec, chan_spec],
    )
    y, out, h = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((seq, dn), f32),
                   jax.ShapeDtypeStruct((seq, dn), f32),
                   jax.ShapeDtypeStruct((n, dn), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(jnp.reshape(length, (1,)).astype(jnp.int32),
      x.astype(f32), dt.astype(f32), z.astype(f32),
      b.astype(f32)[:, :, None], c.astype(f32)[:, :, None],
      a.astype(f32), d.astype(f32)[None], h0.astype(f32))
    return y.astype(x.dtype), out.astype(x.dtype), h


def ssm_scan(x, dt, a, b, c, d, z, h0, length):
    """The prefill recurrence of ONE stream.

    x:      (S, Dn)  the conv'd, silu'd input
    dt:     (S, Dn)  before the softplus (its bias added)
    a:      (N, Dn)  ``-exp(A_log)``
    b, c:   (S, N)
    d:      (Dn,)
    z:      (S, Dn)  the gate's input
    h0:     (N, Dn)  float32 state before position 0
    length: ()       int32 — positions >= length leave the state alone

    Returns ``(y (S, Dn), out (S, Dn), h (N, Dn) float32)``: ``y`` before
    the gate (``D x`` included), ``out = y * silu(z)``, and the state after
    position ``length - 1``."""
    if _scan_shapes_ok(x, a):
        return lax.platform_dependent(
            x, dt, a, b, c, d, z, h0, length,
            tpu=_scan_pallas, default=ssm_scan_reference)
    return ssm_scan_reference(x, dt, a, b, c, d, z, h0, length)


# ------------------------------------------------------------------- decode
def ssm_step_reference(x, dt, a, b, c, d, z, state, slots, layer):
    """The XLA lowering and the oracle of :func:`ssm_step`: gather the B
    slots, one update, scatter them back (rows of padded batch lanes all
    name slot 0, the trash slot)."""
    f32 = jnp.float32
    h = state[layer, slots]                                  # (B, N, Dn)
    h, y, out = _one_step(
        h, x.astype(f32)[:, None], jax.nn.softplus(dt.astype(f32))[:, None],
        a.astype(f32), b.astype(f32)[:, :, None], c.astype(f32)[:, :, None],
        d.astype(f32)[None], z.astype(f32)[:, None])
    return (y[:, 0].astype(x.dtype), out[:, 0].astype(x.dtype),
            state.at[layer, slots].set(h))


def _step_shapes_ok(x, state):
    return x.shape[1] % 128 == 0 and state.shape[2] % 8 == 0


def _step_pallas(x, dt, a, b, c, d, z, state, slots, layer, interpret=False):
    """Grid (B,): a step takes stream i's ``(N, Dn)`` state from slot
    ``slots[i]`` of ``layer`` (the slot ids ride in as a scalar-prefetch
    argument), updates it and writes it back to the block it came from: the
    state array is aliased to the output, so the other slots and layers are
    never read or written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bsz, dn = x.shape
    n = state.shape[2]

    def kernel(slot_ref, x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
               h_ref, y_ref, o_ref, h_out):
        del slot_ref
        h, y, out = _one_step(
            h_ref[...], x_ref[...], jax.nn.softplus(dt_ref[...]),
            a_ref[...], b_ref[...], c_ref[...], d_ref[...], z_ref[...])
        h_out[...] = h
        y_ref[...] = y
        o_ref[...] = out

    row_spec = pl.BlockSpec((None, 1, dn), lambda i, s: (i, 0, 0))
    col_spec = pl.BlockSpec((None, n, 1), lambda i, s: (i, 0, 0))
    slot_spec = pl.BlockSpec((None, None, n, dn),
                             lambda i, s: (layer, s[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz,),
        in_specs=[row_spec, row_spec, row_spec, col_spec, col_spec,
                  pl.BlockSpec((n, dn), lambda i, s: (0, 0)),
                  pl.BlockSpec((1, dn), lambda i, s: (0, 0)), slot_spec],
        out_specs=[row_spec, row_spec, slot_spec],
    )
    y, out, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, 1, dn), f32),
                   jax.ShapeDtypeStruct((bsz, 1, dn), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the slot ids) is the state; result 2 is the state
        input_output_aliases={8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_step",
    )(slots.astype(jnp.int32), x.astype(f32)[:, None], dt.astype(f32)[:, None],
      z.astype(f32)[:, None], b.astype(f32)[:, :, None],
      c.astype(f32)[:, :, None], a.astype(f32), d.astype(f32)[None], state)
    return y[:, 0].astype(x.dtype), out[:, 0].astype(x.dtype), state


def ssm_step(x, dt, a, b, c, d, z, state, slots, layer):
    """One decode token for each of B streams, states updated in place.

    x, dt, z: (B, Dn)   as :func:`ssm_scan`'s rows
    a:        (N, Dn);  d: (Dn,)
    b, c:     (B, N)
    state:    (Ls, NS, N, Dn) float32 — every state layer's slots (donated)
    slots:    (B,) int32 — each stream's slot (padded rows: 0, the trash)
    layer:    static index into ``Ls``

    Returns ``(y (B, Dn), out (B, Dn), state)``."""
    if state.dtype != jnp.float32:
        raise ValueError("the SSM state is kept in float32, not %s"
                         % state.dtype)
    if _step_shapes_ok(x, state):
        return lax.platform_dependent(
            x, dt, a, b, c, d, z, state, slots,
            tpu=functools.partial(_step_pallas, layer=int(layer)),
            default=functools.partial(ssm_step_reference, layer=int(layer)))
    return ssm_step_reference(x, dt, a, b, c, d, z, state, slots, int(layer))
