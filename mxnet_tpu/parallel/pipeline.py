"""Pipeline parallelism (pp) — GPipe-style microbatch pipelining over a mesh
axis.

The reference's only inter-layer parallelism is ctx_group placement
(reference: example/model-parallel-lstm + PlaceDevice, graph_executor.cc:
245-334), where the async engine overlaps stages opportunistically with no
microbatch schedule. The TPU-native form is explicit: stages are sharded over
the ``pp`` mesh axis, activations flow stage-to-stage with ``lax.ppermute``
over ICI, and a ``lax.scan`` over ticks runs the classic GPipe fill/steady/
drain schedule. Backward works by jax autodiff through the scan + ppermute
(the transpose of a ppermute is the reverse ppermute), so one ``jax.grad``
over ``pipeline_apply`` gives 1F1B-equivalent compute without hand-written
schedules.

Stages may be HETEROGENEOUS: pass a list of per-stage functions with
per-stage parameter pytrees (each device traces a ``lax.switch`` over the
stage bodies and executes only its own). The microbatch INPUT shape is free —
stage 0 consumes raw microbatches directly — while inter-stage activations
(and therefore the final outputs, which ride the same ppermute carry) share
one shape; put reshapes inside the first/last stage.

Memory note: the homogeneous (stacked-leaves) mode shards parameters over the
``pp`` axis — each device holds 1/S of the weights, the configuration that
fits a model too big for one device. The heterogeneous mode replicates every
stage's pytree to all devices (devices read only their own stage): it
pipelines compute, not parameter memory.
"""
from __future__ import annotations

__all__ = ["pipeline_apply"]


def _shard_map(fn, mesh, in_specs, out_specs):
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pipeline_apply(stage_fn, stage_params, xs, mesh, axis="pp",
                   carry_shape=None, carry_dtype=None):
    """Run ``S`` pipeline stages over mesh axis ``axis`` on ``M`` microbatches.

    Parameters
    ----------
    stage_fn : either ONE callable ``(params, x) -> y`` shared by all stages,
        or a LIST of ``S`` callables (heterogeneous stages). Stage 0 receives
        the raw microbatch ``xs[m]``; later stages receive the previous
        stage's activation. Every stage's OUTPUT must have the common carry
        shape.
    stage_params : with a shared ``stage_fn``: a pytree whose leaves have
        leading axis ``S`` (stacked per stage), sharded so each device along
        ``axis`` holds its stage's slice. With a list of stage fns: a list of
        ``S`` per-stage pytrees (each replicated to every device; each device
        reads only its own stage's entry).
    xs : array ``(M, ...)`` of microbatches (replicated; any shape).
    mesh : jax Mesh with an ``axis`` dimension of size ``S``.
    carry_shape/carry_dtype : shape/dtype of one inter-stage activation.
        Required when it differs from one microbatch's shape.

    Returns ``(M,) + carry_shape`` outputs (replicated — the last stage's
    results are broadcast back so the loss can be computed data-parallel).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    S = mesh.shape[axis]
    M = xs.shape[0]
    heterogeneous = isinstance(stage_fn, (list, tuple))
    if heterogeneous and len(stage_fn) != S:
        raise ValueError(
            f"got {len(stage_fn)} stage fns for a {S}-way '{axis}' mesh axis"
        )
    if carry_shape is None:
        carry_shape = xs.shape[1:]
    carry_dtype = carry_dtype or xs.dtype

    def local(params, xs_local):
        idx = jax.lax.axis_index(axis)
        T = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]
        zero = jnp.zeros(carry_shape, carry_dtype)
        outs0 = jnp.zeros((M,) + tuple(carry_shape), carry_dtype)

        if heterogeneous:
            def run_stage(recv, t):
                # every branch closes over its own stage's params; only the
                # branch for this device's stage index executes
                branches = []
                for s, fn in enumerate(stage_fn):
                    if s == 0:
                        branches.append(
                            lambda recv, t, _fn=fn, _p=params[0]:
                                _fn(_p, xs_local[jnp.clip(t, 0, M - 1)])
                        )
                    else:
                        branches.append(
                            lambda recv, t, _fn=fn, _p=params[s]: _fn(_p, recv)
                        )
                return jax.lax.switch(idx, branches, recv, t)
        else:
            # stacked leaves: (1, ...) per device -> this stage's slice
            params_here = jax.tree_util.tree_map(lambda a: a[0], params)

            def run_stage(recv, t):
                x_in = jnp.where(
                    idx == 0,
                    jnp.asarray(xs_local[jnp.clip(t, 0, M - 1)], carry_dtype),
                    recv,
                )
                return stage_fn(params_here, x_in)

        def tick(carry, t):
            recv, outs = carry
            y = run_stage(recv, t)
            nxt = jax.lax.ppermute(y, axis, perm)
            # microbatch m = t-(S-1) finishes at the last stage on tick t
            m = t - (S - 1)
            mslot = jnp.maximum(m, 0)
            take = (idx == S - 1) & (m >= 0)
            outs = outs.at[mslot].set(jnp.where(take, y, outs[mslot]))
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (zero, outs0), jnp.arange(T))
        # broadcast finished outputs from the last stage to every stage
        outs = jax.lax.psum(jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)),
                            axis)
        return outs

    if heterogeneous:
        # per-stage pytrees stay replicated; devices index their own stage
        pspec = jax.tree_util.tree_map(lambda _: P(), list(stage_params))
    else:
        # other mesh axes (dp etc.) are untouched: specs name only the pp axis
        pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = _shard_map(local, mesh, in_specs=(pspec, P()), out_specs=P())
    return fn(list(stage_params) if heterogeneous else stage_params, xs)
