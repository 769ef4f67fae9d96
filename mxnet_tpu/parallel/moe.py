"""Expert parallelism (ep) — a Switch-style mixture-of-experts FFN with
experts sharded over a mesh axis and token routing via ``lax.all_to_all``.

Not present in the reference (SURVEY §2.5: EP is new design work for the TPU
build). The design is the standard TPU MoE recipe: a replicated router picks
top-1 experts, tokens are packed into per-expert capacity slots with one-hot
dispatch einsums (MXU-friendly — no gather/scatter), an all_to_all over the
``ep`` axis carries each token group to the device owning its expert, the
expert FFN runs as a batched matmul over its local tokens, and a reverse
all_to_all + weighted combine returns results. Dropped tokens (over capacity)
pass through with zero contribution, as in Switch Transformers.
"""
from __future__ import annotations

__all__ = ["moe_ffn", "moe_ffn_local"]


def _default_expert_fn(params, xe):
    """The stock Switch expert body: a 2-layer relu FFN.
    params: (w1 (D,H), w2 (H,D)); xe: (C', D) one expert's tokens."""
    import jax
    import jax.numpy as jnp

    from ..ops.registry import fp32_precision

    w1, w2 = params
    prec = fp32_precision(xe.dtype)
    h = jax.nn.relu(jnp.dot(xe, w1, precision=prec))
    return jnp.dot(h, w2, precision=prec)


def moe_ffn_local(x, gate_w, w1, w2, axis, n, capacity_factor=1.25,
                  expert_fn=None, expert_params=None):
    """Per-device body (inside shard_map). x: (B, D) local tokens;
    gate_w: (D, E) replicated; w1: (E/n, D, H), w2: (E/n, H, D) local experts.

    ``expert_fn(params_for_one_expert, tokens (C', D)) -> (C', D)`` replaces
    the stock 2-layer relu body; ``expert_params`` is a pytree whose leaves
    have leading axis E/n (this device's experts) — vmapped over experts.
    When given, w1/w2 are ignored (pass the gate plus your own params)."""
    import jax
    import jax.numpy as jnp

    from ..ops.registry import fp32_precision

    B, D = x.shape
    if expert_fn is None:
        expert_fn = _default_expert_fn
        expert_params = (w1, w2)
        E_local = w1.shape[0]
    else:
        E_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    E = E_local * n
    C = max(int(B * capacity_factor / E), 1)  # capacity per expert per device
    prec = fp32_precision(x.dtype)

    logits = jnp.dot(x, gate_w, precision=prec)  # (B, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # (B,)
    gate = jnp.max(probs, axis=-1)  # (B,)

    onehot = jax.nn.one_hot(expert, E, dtype=x.dtype)  # (B, E)
    # position of each token within its expert's capacity
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # (B, E), -1 elsewhere
    pos_tok = jnp.sum(pos * onehot, axis=1)  # (B,)
    keep = pos_tok < C
    gate = gate * keep.astype(x.dtype)
    # dispatch tensor: (B, E, C) one-hot over (expert, slot)
    slot_oh = jax.nn.one_hot(
        jnp.clip(pos_tok, 0, C - 1).astype(jnp.int32), C, dtype=x.dtype)
    dispatch = onehot[:, :, None] * slot_oh[:, None, :] * keep[:, None, None].astype(x.dtype)
    # pack tokens: (E, C, D)
    xe = jnp.einsum("bec,bd->ecd", dispatch, x, precision=prec)
    # route: split the E axis across devices, gather their contributions;
    # result: (E_local, n*C, D) — my experts' slots from every device
    xe = xe.reshape(n, E_local, C, D)
    xe = jax.lax.all_to_all(xe, axis, split_axis=0, concat_axis=0, tiled=False)
    xe = jnp.moveaxis(xe, 0, 1).reshape(E_local, n * C, D)
    # expert body, vmapped over this device's experts (batched MXU matmuls
    # for the stock FFN; arbitrary jax for a custom body)
    ye = jax.vmap(expert_fn)(expert_params, xe)  # (E_local, n*C, D)
    # route back
    ye = jnp.moveaxis(ye.reshape(E_local, n, C, D), 1, 0)
    ye = jax.lax.all_to_all(ye, axis, split_axis=0, concat_axis=0, tiled=False)
    ye = ye.reshape(E, C, D)
    # combine: weight each token's slot output by its gate
    combine = dispatch * gate[:, None, None]  # (B, E, C)
    return jnp.einsum("bec,ecd->bd", combine, ye, precision=prec)


def moe_ffn(x, gate_w, w1, w2, mesh, axis="ep", capacity_factor=1.25,
            expert_fn=None, expert_params=None):
    """Expert-parallel Switch FFN over ``mesh[axis]``.

    x: (N, D) tokens sharded over ``axis`` (each device gets N/n);
    gate_w: (D, E) replicated; w1: (E, D, H), w2: (E, H, D) sharded over
    ``axis`` (each device owns E/n experts). Returns (N, D) sharded like x.

    A custom expert body: pass ``expert_fn(params_one_expert, tokens) ->
    tokens`` plus ``expert_params`` (pytree, leading axis E, sharded over
    ``axis``); w1/w2 may then be None.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    custom = expert_fn is not None
    if custom and expert_params is None:
        raise ValueError("expert_fn requires expert_params")
    if not custom and (w1 is None or w2 is None):
        raise ValueError("pass w1/w2 for the stock FFN or expert_fn+expert_params")

    if custom:
        ep_spec = jax.tree_util.tree_map(lambda _: P(axis), expert_params)

        def body(xl, gw, epp):
            return moe_ffn_local(xl, gw, None, None, axis, n,
                                 capacity_factor=capacity_factor,
                                 expert_fn=expert_fn, expert_params=epp)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(), ep_spec),
            out_specs=P(axis),
            check_vma=False,
        )
        return fn(x, gate_w, expert_params)

    def body(xl, gw, w1l, w2l):
        return moe_ffn_local(xl, gw, w1l, w2l, axis, n,
                             capacity_factor=capacity_factor)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return fn(x, gate_w, w1, w2)
