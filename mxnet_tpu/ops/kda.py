"""Gated delta-rule linear attention (Kimi Delta Attention, arXiv:2510.26692)
for serving: a MATRIX state a head, rewritten by a rank-one update a token.

Per head, with ``q_t, k_t`` in R^dk (``k_t`` of unit length), ``v_t`` in R^dv,
a log decay ``g_t <= 0`` a key channel (``alpha_t = exp(g_t)``) and a step
size ``beta_t``, the state ``S`` in R^(dk x dv) (zero at the stream's start)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

One token (decode, :func:`kda_step`). Write ``S' = Diag(alpha_t) S_{t-1}`` and
``u_t = beta_t (v_t - S'^T k_t)``; then ``S_t = S' + k_t u_t^T``: the decay, the
row ``k^T S'``, the rank-one update and ``S^T q`` are ONE pass over the state.

A chunk of C tokens (prefill, :func:`kda_chunk`), from the recurrence. Let
``S_0`` be the state the chunk starts from, ``G_r = sum_{i<=r} g_i`` the
cumulative log decay inside the chunk and ``Gamma_r = exp(G_r)``. Unrolling
``S_r = Diag(alpha_r) S_{r-1} + k_r u_r^T`` gives

    S_r = Diag(Gamma_r) S_0 + sum_{i<=r} (k_i (.) exp(G_r - G_i)) u_i^T      (1)

and ``u_r = beta_r (v_r - (Diag(alpha_r) S_{r-1})^T k_r)``; with (1) at
``r - 1`` decayed one step more (``alpha_r exp(G_{r-1} - G_i) = exp(G_r -
G_i)``)

    u_r = beta_r (v_r - S_0^T (k_r (.) Gamma_r) - sum_{i<r} A_ri u_i),
    A_ri = sum_d k_{r,d} k_{i,d} exp(G_{r,d} - G_{i,d})   (i < r, else 0)

which is the unit lower-triangular system ``(I + Diag(beta) A) U = Diag(beta)
(V - (K (.) Gamma) S_0)``. With ``U`` known, (1) gives the outputs and the
chunk's last state:

    o_r = S_0^T (q_r (.) Gamma_r) + sum_{i<=r} P_ri u_i,
    P_ri = sum_d q_{r,d} k_{i,d} exp(G_{r,d} - G_{i,d})   (i <= r)
    S_C = Diag(Gamma_C) S_0 + sum_i (k_i (.) exp(G_C - G_i)) u_i^T

Every exponent above is a DIFFERENCE of two cumulative sums and at most 0:
``1 / Gamma_i`` alone is never formed (nothing bounds the gate from below: 64
steps at a decay of e^-1.6 underflow it). The kernel gets ``A`` and ``P``
through matmuls all the same: rows ``r`` of a 16-row group and columns ``i``
before the group split the exponent at the group's edge ``e`` (``G_r - G_e``
and ``G_e - G_i``, both at most 0); inside a group the 16 x 16 pairs are
taken directly. The triangular system is solved by inverting ``I + Diag(beta)
A`` by halves (``[[L1, 0], [L21, L2]]^-1 = [[X1, 0], [-X2 L21 X1, X2]]``: seven
levels of two 128 x 128 matmuls from the diagonal up), which is a forward
substitution in blocks: no power of ``A`` is formed, so nothing grows where
neighbouring keys are alike.

Rows at or past ``length`` leave the state as it is (``g = 0``, ``beta = 0``).

Two entry points, both serving-only (no vjp), platform chosen at LOWERING time
as in ``ops/ssm.py``: a Pallas kernel on the TPU (custom calls ``kda_step`` and
``kda_chunk``), plain XLA elsewhere (the chunkwise form there too, ``A`` and
``P`` taken directly, the system by a triangular solve).
:func:`kda_recurrence` is the token-by-token scan, the oracle of both.
Everything inside is float32: the state, the decay, the sums. ``o`` comes back
in ``v``'s type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["CHUNK", "kda_chunk", "kda_chunk_reference", "kda_recurrence",
           "kda_step", "kda_step_reference"]

#: rows a grid step of the prefill kernel holds: one MXU tile each way, so
#: that ``A``, ``P`` and the inverse are whole 128 x 128 matrices (at 64 the
#: inverse's matmuls would be quarter tiles and a 4,096-token prompt twice
#: the sequential steps; at 256 the solve doubles a row's work)
CHUNK = 128
_GROUP = 16         # rows whose pairs with each other are taken directly
_HEAD_BLOCK = 16    # heads a grid step of the decode kernel holds (1 MB)
_HI = lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _one_step(state, q, k, v, g, beta):
    """One token of the recurrence, float32. state (.., dk, dv); q, k, g
    (.., dk); v (.., dv); beta (..,). Returns (state, o (.., dv))."""
    state = jnp.exp(g)[..., None] * state
    u = beta[..., None] * (v - jnp.einsum("...k,...kv->...v", k, state,
                                          precision=_HI))
    state = state + k[..., None] * u[..., None, :]
    return state, jnp.einsum("...k,...kv->...v", q, state, precision=_HI)


def kda_recurrence(q, k, v, g, beta, state0, length=None):
    """The recurrence, a token at a time (``lax.scan``): the oracle of the
    chunkwise form. q, k, g ``(S, H, dk)``; v ``(S, H, dv)``; beta ``(S, H)``;
    state0 ``(H, dk, dv)`` float32. Returns ``(o (S, H, dv), state)``."""
    f32 = jnp.float32
    g, beta = _live_rows(g, beta, q.shape[0] if length is None else length)

    def step(state, xs):
        return _one_step(state, *xs)

    state, o = lax.scan(step, state0.astype(f32), (
        q.astype(f32), k.astype(f32), v.astype(f32), g, beta))
    return o.astype(v.dtype), state


# ------------------------------------------------------------------ prefill
def _live_rows(g, beta, length):
    """float32 ``g`` and ``beta`` with the rows at or past ``length`` made
    rows that leave the state alone."""
    f32 = jnp.float32
    live = jnp.arange(g.shape[0]) < length
    return (jnp.where(live[:, None, None], g.astype(f32), 0.0),
            jnp.where(live[:, None], beta.astype(f32), 0.0))


def _chunked(t, chunk):
    """``(S, H, d)`` padded with zero rows to whole chunks: ``(n, H, C, d)``."""
    seq = t.shape[0]
    t = jnp.pad(t, ((0, -seq % chunk),) + ((0, 0),) * (t.ndim - 1))
    t = t.reshape((t.shape[0] // chunk, chunk) + t.shape[1:])
    return jnp.swapaxes(t, 1, 2)


def kda_chunk_reference(q, k, v, g, beta, length, state, slot, layer,
                        chunk=CHUNK):
    """The XLA lowering of :func:`kda_chunk`: the chunkwise form of the
    module docstring, a ``lax.scan`` over chunks, every head at once; ``A``
    and ``P`` from the pairs' exponents directly, the system by a triangular
    solve."""
    f32 = jnp.float32
    seq, heads, dk = q.shape
    chunk = min(chunk, seq)
    g, beta = _live_rows(g, beta, length)
    at = jnp.arange(chunk)
    before = at[None, :] < at[:, None]                  # (r, i): i < r
    upto = at[None, :] <= at[:, None]

    def one(s0, xs):
        qc, kc, vc, gc, bc = xs                 # (H, C, d); bc (H, C, 1)
        cum = jnp.cumsum(gc, axis=1)
        diff = cum[:, :, None, :] - cum[:, None, :, :]      # (H, r, i, dk)
        decay = jnp.exp(jnp.where(upto[None, :, :, None], diff, -jnp.inf))
        a = jnp.einsum("hrd,hid,hrid->hri", kc, kc, decay, precision=_HI)
        p = jnp.einsum("hrd,hid,hrid->hri", qc, kc, decay, precision=_HI)
        gamma = jnp.exp(cum)
        w = bc * (vc - jnp.einsum("hrd,hdv->hrv", kc * gamma, s0,
                                  precision=_HI))
        lower = jnp.eye(chunk, dtype=f32) + bc * jnp.where(before, a, 0.0)
        u = jax.scipy.linalg.solve_triangular(lower, w, lower=True,
                                              unit_diagonal=True)
        o = jnp.einsum("hrd,hdv->hrv", qc * gamma, s0, precision=_HI) \
            + jnp.einsum("hri,hiv->hrv", jnp.where(upto, p, 0.0), u,
                         precision=_HI)
        last = cum[:, -1:, :]
        s1 = jnp.exp(last[:, 0, :, None]) * s0 + jnp.einsum(
            "hid,hiv->hdv", kc * jnp.exp(last - cum), u, precision=_HI)
        return s1, o

    s, o = lax.scan(one, jnp.zeros((heads, dk, v.shape[-1]), f32), (
        _chunked(q.astype(f32), chunk), _chunked(k.astype(f32), chunk),
        _chunked(v.astype(f32), chunk), _chunked(g, chunk),
        _chunked(beta[..., None], chunk)))
    o = jnp.swapaxes(o, 1, 2).reshape((-1,) + v.shape[1:])[:seq]
    return o.astype(v.dtype), state.at[layer, slot].set(s)


def _kernel_takes(ok, what):
    """Whether the Pallas kernel takes these shapes. Where it does not, the
    XLA lowering serves the CPU (the tests' tiny models); on a TPU that
    would be a model measured through the oracle with no kernel's name on
    its trace, so it is refused there."""
    if not ok and jax.default_backend() == "tpu":
        raise ValueError("%s: the kernel takes whole (8, 128) tiles a head "
                         "and heads in blocks of %d" % (what, _HEAD_BLOCK))
    return ok


def _chunk_body(q_ref, k_ref, kb_ref, vb_ref, g32, o_ref, s_ref, k32):
    """One live chunk of one head inside :func:`_chunk_pallas`'s kernel:
    ``s_ref`` holds ``S_0`` and is left holding ``S_C``."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    c, dk = k32.shape
    levels = c.bit_length() - 1
    s0 = s_ref[...]
    k32[...] = k_ref[...].astype(f32)     # single rows are read from it
    kk, cumv = k32[...], g32[...]
    qq, kb, vb = (r[...].astype(f32) for r in (q_ref, kb_ref, vb_ref))
    gamma = jnp.exp(cumv)
    w = vb - _dot(kb * gamma, s0)
    o = _dot(qq * gamma, s0)

    lane = lax.broadcasted_iota(jnp.int32, (_GROUP, c), 1)
    row = lax.broadcasted_iota(jnp.int32, (_GROUP, c), 0)
    a_rows, p_rows = [], []
    for r0 in range(0, c, _GROUP):
        here = slice(r0, r0 + _GROUP)
        cg, kbg, qg = cumv[here], kb[here], qq[here]
        an = jnp.zeros((_GROUP, c), f32)
        pn = an
        if r0:
            # columns before the group: the exponent split at its edge
            edge = g32[pl.ds(r0 - 1, 1), :]
            keys = kk * jnp.exp(jnp.minimum(edge - cumv, 0.0))
            since = jnp.exp(cg - edge)
            both = lax.dot_general(
                jnp.concatenate([kbg * since, qg * since], 0), keys,
                (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=f32)             # (2 GROUP, C)
            an = jnp.where(lane < r0, both[:_GROUP], 0.0)
            pn = jnp.where(lane < r0, both[_GROUP:], 0.0)
        for j in range(_GROUP):
            kj = k32[pl.ds(r0 + j, 1), :]
            gj = g32[pl.ds(r0 + j, 1), :]
            wj = kj * jnp.exp(jnp.minimum(cg - gj, 0.0))
            hit = lane == r0 + j
            an = jnp.where(hit & (row > j),
                           jnp.sum(kbg * wj, -1, keepdims=True), an)
            pn = jnp.where(hit & (row >= j),
                           jnp.sum(qg * wj, -1, keepdims=True), pn)
        a_rows.append(an)
        p_rows.append(pn)
    lower = jnp.concatenate(a_rows, 0)      # Diag(beta) A, strictly lower
    pmat = jnp.concatenate(p_rows, 0)

    # (I + lower)^-1 by halves, from the diagonal up
    ri = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    inv = jnp.where(ri == ci, 1.0, 0.0).astype(f32)
    for level in range(levels):
        pair = (ri >> (level + 1)) == (ci >> (level + 1))
        off = pair & (((ri >> level) & 1) == 1) \
            & (((ci >> level) & 1) == 0)
        inv = inv - _dot(inv, _dot(jnp.where(off, lower, 0.0), inv))
    u = _dot(inv, w)
    o_ref[...] = (o + _dot(pmat, u)).astype(o_ref.dtype)

    last = g32[pl.ds(c - 1, 1), :]                          # (1, dk)
    left = (kk * jnp.exp(last - cumv)).T                    # (dk, C)
    col = jnp.broadcast_to(jnp.exp(last), (dk, dk)).T[:, :1]
    s_ref[...] = col * s0 + _dot(left, u)


def _chunk_pallas(q, k, v, g, beta, length, state, slot, layer,
                  interpret=False):
    """Grid (heads, chunks), chunks innermost: a head's ``(dk, dv)`` state
    lives in the state output's VMEM block across its chunks (the block's
    index does not move with time) and is written to the stream's slot once,
    after the last; a chunk wholly at or past ``length`` (the bucket's
    padding) does nothing but zero its rows of ``o``. ``beta`` is folded
    into the rows outside (``beta k``, ``beta v``: the kernel needs it as a
    column nowhere) and the cumulative decay is taken outside, a chunk at a
    time."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    seq, heads, dk = q.shape
    dv = v.shape[-1]
    c = CHUNK
    g, beta = _live_rows(g, beta, length)
    act = v.dtype
    bcol = beta[..., None]

    def rows(t):
        """``(S, H, d)`` -> ``(H, S', d)``, whole chunks of zero-padded
        rows."""
        x = _chunked(t, c)
        return jnp.swapaxes(x, 0, 1).reshape(heads, -1, x.shape[-1])

    cum = jnp.cumsum(_chunked(g, c), axis=2)                # (n, H, C, dk)
    cum = jnp.swapaxes(cum, 0, 1).reshape(heads, -1, dk)
    operands = (rows(q.astype(act)), rows(k.astype(act)),
                rows((k.astype(f32) * bcol).astype(act)),
                rows((v.astype(f32) * bcol).astype(act)), cum)
    n_chunks = cum.shape[1] // c

    def kernel(meta_ref, q_ref, k_ref, kb_ref, vb_ref, g32, s_in, o_ref,
               s_ref, k32):
        del s_in
        live = pl.program_id(1) * c < meta_ref[1]

        @pl.when(pl.program_id(1) == 0)
        def _start():
            s_ref[...] = jnp.zeros_like(s_ref)

        @pl.when(jnp.logical_not(live))
        def _past():        # a chunk of the bucket's padding: no work
            o_ref[...] = jnp.zeros_like(o_ref)

        pl.when(live)(functools.partial(
            _chunk_body, q_ref, k_ref, kb_ref, vb_ref, g32, o_ref, s_ref, k32))

    def row_spec(d):
        return pl.BlockSpec((None, c, d), lambda h, t, s: (h, t, 0))

    state_spec = pl.BlockSpec((None, None, None, dk, dv),
                              lambda h, t, s: (layer, s[0], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads, n_chunks),
        in_specs=[row_spec(dk), row_spec(dk), row_spec(dk), row_spec(dv),
                  row_spec(dk), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row_spec(dv), state_spec],
        scratch_shapes=[pltpu.VMEM((c, dk), f32)],
    )
    o, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((heads, n_chunks * c, dv), act),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (after slot and length) is the state; result 1 is it too
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(jnp.stack([jnp.asarray(slot, jnp.int32),
                 jnp.asarray(length, jnp.int32)]), *operands, state)
    return jnp.swapaxes(o, 0, 1)[:seq], state


def kda_chunk(q, k, v, g, beta, length, state, slot, layer):
    """The prefill of ONE stream from an empty state.

    q, k:   (S, H, dk)  ``k`` of unit length a head, ``q`` scaled
    v:      (S, H, dv)
    g:      (S, H, dk)  float32 log decay, at most 0
    beta:   (S, H)      float32 step size
    length: ()          int32 — rows >= length leave the state alone
    state:  (Ls, NS, H, dk, dv) float32 — every linear layer's slots (donated)
    slot:   ()          int32 — the stream's slot
    layer:  static index into ``Ls``

    Returns ``(o (S, H, dv)`` in ``v``'s type, ``state)``: the state after row
    ``length - 1`` written into ``state[layer, slot]``, nothing else of it
    touched."""
    if state.dtype != jnp.float32:
        raise ValueError("the delta rule's state is kept in float32, not %s"
                         % state.dtype)
    layer = int(layer)
    if _kernel_takes(q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0,
                     "kda_chunk of %s keys, %s values" % (q.shape, v.shape)):
        return lax.platform_dependent(
            q, k, v, g, beta, length, state, slot,
            tpu=functools.partial(_chunk_pallas, layer=layer),
            default=functools.partial(kda_chunk_reference, layer=layer))
    return kda_chunk_reference(q, k, v, g, beta, length, state, slot, layer)


# ------------------------------------------------------------------- decode
def kda_step_reference(q, k, v, g, beta, state, slots, layer):
    """The XLA lowering and the oracle of :func:`kda_step`: gather the B
    slots, one update, scatter them back (rows of padded batch lanes all
    name slot 0, the trash slot)."""
    f32 = jnp.float32
    s, o = _one_step(state[layer, slots], q.astype(f32), k.astype(f32),
                     v.astype(f32), g.astype(f32), beta.astype(f32))
    return o.astype(v.dtype), state.at[layer, slots].set(s)


def _step_pallas(q, k, v, g, beta, state, slots, layer, interpret=False):
    """Grid (B, head blocks): a step takes ``hb`` heads of stream i's state
    from slot ``slots[i]`` of ``layer`` (the slot ids ride in as a
    scalar-prefetch argument), updates them and writes them back to the block
    they came from: the state array is aliased to the output, so the other
    slots and layers are never read or written. What multiplies a state's
    ROWS — the decay, ``k``, ``beta k`` and ``q`` — comes in as columns, the
    four side by side a head block ``(dk, 4 hb)``; the sums over ``dk`` run
    down the sublanes on the VPU (a matmul of one row would load every
    head's state into the MXU for one row of work)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bsz, heads, dk = q.shape
    dv = v.shape[-1]
    hb = _HEAD_BLOCK
    nb = heads // hb
    beta = beta.astype(f32)[..., None]
    kf = k.astype(f32)
    cols = jnp.stack([jnp.exp(g.astype(f32)), kf, kf * beta, q.astype(f32)],
                     axis=1)                                # (B, 4, H, dk)
    cols = cols.reshape(bsz, 4, nb, hb, dk).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(bsz, nb, dk, 4 * hb)
    bv = (v.astype(f32) * beta).reshape(bsz, nb, hb, dv)

    def kernel(slot_ref, c_ref, bv_ref, s_ref, o_ref, s_out):
        del slot_ref
        cols = c_ref[...]
        for h in range(hb):
            decay, kc, bk, qc = (cols[:, j * hb + h:j * hb + h + 1]
                                 for j in range(4))         # (dk, 1) each
            s = decay * s_ref[h]
            u = bv_ref[pl.ds(h, 1), :] - jnp.sum(bk * s, 0, keepdims=True)
            s = s + kc * u
            s_out[h] = s
            o_ref[pl.ds(h, 1), :] = jnp.sum(qc * s, 0, keepdims=True)

    slot_spec = pl.BlockSpec((None, None, hb, dk, dv),
                             lambda i, j, s: (layer, s[i], j, 0, 0))
    row_spec = pl.BlockSpec((None, None, hb, dv), lambda i, j, s: (i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, nb),
        in_specs=[pl.BlockSpec((None, None, dk, 4 * hb),
                               lambda i, j, s: (i, j, 0, 0)),
                  row_spec, slot_spec],
        out_specs=[row_spec, slot_spec],
    )
    o, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, nb, hb, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 3 (after the slot ids) is the state; result 1 is the state
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_step",
    )(slots.astype(jnp.int32), cols, bv, state)
    return o.reshape(bsz, heads, dv).astype(v.dtype), state


def kda_step(q, k, v, g, beta, state, slots, layer):
    """One decode token for each of B streams, states updated in place.

    q, k, g: (B, H, dk)  as :func:`kda_chunk`'s rows
    v:       (B, H, dv);  beta: (B, H)
    state:   (Ls, NS, H, dk, dv) float32 — every linear layer's slots
             (donated)
    slots:   (B,) int32 — each stream's slot (padded rows: 0, the trash)
    layer:   static index into ``Ls``

    Returns ``(o (B, H, dv)`` in ``v``'s type, ``state)``."""
    if state.dtype != jnp.float32:
        raise ValueError("the delta rule's state is kept in float32, not %s"
                         % state.dtype)
    layer = int(layer)
    if _kernel_takes(q.shape[-1] % 8 == 0 and v.shape[-1] % 128 == 0
                     and q.shape[1] % _HEAD_BLOCK == 0,
                     "kda_step of %s keys, %s values" % (q.shape, v.shape)):
        return lax.platform_dependent(
            q, k, v, g, beta, state, slots,
            tpu=functools.partial(_step_pallas, layer=layer),
            default=functools.partial(kda_step_reference, layer=layer))
    return kda_step_reference(q, k, v, g, beta, state, slots, layer)
