"""Parallel decoder-only LM training — sp / pp / ep as USABLE components.

The reference has no counterpart (SURVEY §2.5: sequence/pipeline/expert
parallelism are new design work for the TPU build); models/transformer_lm.py
is the symbol-level flagship, and this module is the explicitly-parallel
training harness for the same architecture family, built directly on the
mesh primitives:

* ``SPLMTrainer`` — sequence parallelism: activations sharded over the
  sequence dim on an ``sp`` axis, attention runs as a ring over ICI
  (parallel/ring.py ring_attention_local). This is the long-context mode: a
  sequence S costs each device O(S/n) activation memory.
* ``PPLMTrainer`` — pipeline parallelism: transformer blocks split into
  heterogeneous stages over a ``pp`` axis (parallel/pipeline.py GPipe
  schedule); stage 0 owns the embedding, the loss head runs replicated on the
  microbatch outputs.
* ``MoELMTrainer`` — expert parallelism: each block's FFN is a Switch
  mixture-of-experts sharded over an ``ep`` axis (parallel/moe.py), batch
  sharded on the same axis so the all_to_all carries token groups over ICI.

Every trainer exposes the same surface: ``init_params(seed)``,
``step(params, opt_state, tokens, labels) -> (params, opt_state, loss)``
(jit-compiled, optimizer fused in-graph via parallel/fused_opt rules), and
``forward(params, tokens) -> logits`` for evaluation/parity checks. Optimizer
selection matches SPMDTrainer (registry names + lr_scheduler; unsupported
optimizers raise).
"""
from __future__ import annotations

import numpy as np

from . import fused_opt

__all__ = ["DenseLMTrainer", "SPLMTrainer", "PPLMTrainer", "MoELMTrainer",
           "init_lm_params", "lm_param_names", "lm_forward_dense"]


# ---------------------------------------------------------------- params
def init_lm_params(seed, vocab_size, num_layers, model_dim, num_heads,
                   ffn_dim, seq_len, num_experts=0, dtype=np.float32):
    """Parameter dict for the pure-jax LM family. Names follow
    models/transformer_lm.py's layer naming so the two stories read as one
    (layer{i}_ln1_gamma, layer{i}_attn_in_weight, layer{i}_ffn1_weight, ...).

    With ``num_experts > 0`` each layer's FFN becomes a Switch MoE:
    layer{i}_gate_weight (D, E), layer{i}_ffn1_weight (E, D, F),
    layer{i}_ffn2_weight (E, F, D).
    """
    rng = np.random.RandomState(seed)
    D, F, V, T = model_dim, ffn_dim, vocab_size, seq_len

    def normal(*shape, scale=0.02):
        return (rng.randn(*shape) * scale).astype(dtype)

    p = {
        "embed_weight": normal(V, D),
        "pos_embed_weight": normal(1, T, D),
        "final_ln_gamma": np.ones(D, dtype),
        "final_ln_beta": np.zeros(D, dtype),
        "lm_head_weight": normal(D, V),
    }
    for i in range(num_layers):
        n = "layer%d_" % i
        p[n + "ln1_gamma"] = np.ones(D, dtype)
        p[n + "ln1_beta"] = np.zeros(D, dtype)
        p[n + "ln2_gamma"] = np.ones(D, dtype)
        p[n + "ln2_beta"] = np.zeros(D, dtype)
        p[n + "attn_in_weight"] = normal(D, 3 * D)
        p[n + "attn_out_weight"] = normal(D, D)
        if num_experts:
            p[n + "gate_weight"] = normal(D, num_experts)
            p[n + "ffn1_weight"] = normal(num_experts, D, F)
            p[n + "ffn2_weight"] = normal(num_experts, F, D)
        else:
            p[n + "ffn1_weight"] = normal(D, F)
            p[n + "ffn2_weight"] = normal(F, D)
    return p


def lm_param_names(num_layers, num_experts=0, **_):
    """Parameter NAMES for the LM family without allocating anything (for
    PartitionSpec construction — init_lm_params at large vocab/dim fills GBs)."""
    names = ["embed_weight", "pos_embed_weight", "final_ln_gamma",
             "final_ln_beta", "lm_head_weight"]
    for i in range(num_layers):
        n = "layer%d_" % i
        names += [n + "ln1_gamma", n + "ln1_beta", n + "ln2_gamma",
                  n + "ln2_beta", n + "attn_in_weight", n + "attn_out_weight"]
        if num_experts:
            names.append(n + "gate_weight")
        names += [n + "ffn1_weight", n + "ffn2_weight"]
    return names


def _ln(x, gamma, beta, eps=1e-5):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def _prec(x):
    from ..ops.registry import fp32_precision

    return fp32_precision(x.dtype)


def _qkv(h, w_in, num_heads):
    """(B, T, D) @ (D, 3D) -> three (B, H, T, Dh)."""
    import jax.numpy as jnp

    B, T, D = h.shape
    Dh = D // num_heads
    proj = jnp.einsum("btd,de->bte", h, w_in, precision=_prec(h))
    q, k, v = jnp.split(proj, 3, axis=-1)
    to_heads = lambda a: a.reshape(B, T, num_heads, Dh).transpose(0, 2, 1, 3)
    return to_heads(q), to_heads(k), to_heads(v)


def _merge_heads(a):
    import jax.numpy as jnp

    B, H, T, Dh = a.shape
    return a.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)


def _dense_causal_attention(q, k, v):
    import jax.numpy as jnp

    Dh = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=_prec(q)) / np.sqrt(Dh)
    T = q.shape[2]
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -1e30)
    import jax

    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=_prec(v))


def _block_dense(p, prefix, x, num_heads):
    """One pre-norm block with dense causal attention + dense FFN."""
    import jax
    import jax.numpy as jnp

    h = _ln(x, p[prefix + "ln1_gamma"], p[prefix + "ln1_beta"])
    q, k, v = _qkv(h, p[prefix + "attn_in_weight"], num_heads)
    attn = _merge_heads(_dense_causal_attention(q, k, v))
    x = x + jnp.einsum("btd,de->bte", attn, p[prefix + "attn_out_weight"], precision=_prec(attn))
    h = _ln(x, p[prefix + "ln2_gamma"], p[prefix + "ln2_beta"])
    f = jax.nn.relu(jnp.einsum("btd,df->btf", h, p[prefix + "ffn1_weight"], precision=_prec(h)))
    return x + jnp.einsum("btf,fd->btd", f, p[prefix + "ffn2_weight"], precision=_prec(f))


def lm_forward_dense(params, tokens, num_layers, num_heads):
    """Single-device reference forward (B, T) int tokens -> (B, T, V) logits.
    The oracle the parallel modes are tested against."""
    import jax.numpy as jnp

    x = params["embed_weight"][tokens] + params["pos_embed_weight"][0]
    for i in range(num_layers):
        x = _block_dense(params, "layer%d_" % i, x, num_heads)
    x = _ln(x, params["final_ln_gamma"], params["final_ln_beta"])
    return jnp.einsum("btd,dv->btv", x, params["lm_head_weight"], precision=_prec(x))


def _xent(logits, labels):
    """Mean next-token cross-entropy. labels int (B, T)."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _obs_jit(fn, program, trainer, cfg, **jit_kwargs):
    """compileobs-registered jit for the LM trainers: (trainer class, frozen
    config) is the graph identity, so a re-built trainer over the same
    config diffs against its predecessor's compiled signature while a
    different depth/width registers as a fresh graph."""
    from .. import compileobs

    key = (trainer, tuple(sorted((k, str(v)) for k, v in cfg.items())))
    return compileobs.jit(
        fn, program, site="mxnet_tpu/parallel/lm.py:%s" % trainer,
        graph_key=key, **jit_kwargs)


class _LMTrainerBase:
    """Shared optimizer plumbing: in-graph fused update via fused_opt rules."""

    def __init__(self, optimizer="sgd", optimizer_params=None):
        from .. import optimizer as opt_mod

        if isinstance(optimizer, str):
            self.optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        else:
            self.optimizer = optimizer
        self.rule = fused_opt.make_rule(self.optimizer)

    def init_opt_state(self, params):
        return {
            n: self.rule.init_state(a.shape, a.dtype) for n, a in params.items()
        }

    def _apply_updates(self, params, grads, opt_state, lr, t):
        wd = float(self.optimizer.wd)
        new_p, new_s = {}, {}
        for n in params:
            new_p[n], new_s[n] = self.rule.apply(
                params[n], grads[n], opt_state[n], lr, wd, t
            )
        return new_p, new_s

    def _host_lr_t(self, params):
        lr, t = fused_opt.host_step_values(self.optimizer, list(params))
        return np.float32(lr), np.int32(t)


# ------------------------------------------------------------------ dense
class DenseLMTrainer(_LMTrainerBase):
    """Single-program dense LM trainer — the same step/forward surface as the
    parallel trainers with no mesh, so ``ParallelLMModule(mode='dense')``
    gives the baseline every parallel mode is parity-tested against."""

    def __init__(self, mesh=None, vocab_size=0, num_layers=0, model_dim=0,
                 num_heads=0, ffn_dim=0, seq_len=0, optimizer="sgd",
                 optimizer_params=None, **_):
        super().__init__(optimizer, optimizer_params)
        self.mesh = mesh  # unused; accepted for constructor symmetry
        self.cfg = dict(vocab_size=vocab_size, num_layers=num_layers,
                        model_dim=model_dim, num_heads=num_heads,
                        ffn_dim=ffn_dim, seq_len=seq_len)
        self._step = None
        self._fwd = None

    def init_params(self, seed=0):
        return init_lm_params(seed, **self.cfg)

    def _build(self):
        import jax

        L, H = self.cfg["num_layers"], self.cfg["num_heads"]

        def step(params, opt_state, tokens, labels, lr, t):
            def loss_fn(p):
                return _xent(lm_forward_dense(p, tokens, L, H), labels)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state = self._apply_updates(params, grads, opt_state, lr, t)
            return params, opt_state, loss

        self._step = _obs_jit(step, "lm.step", "DenseLMTrainer",
                              self.cfg, donate_argnums=(0, 1))
        self._fwd = _obs_jit(lambda p, tok: lm_forward_dense(p, tok, L, H),
                             "lm.fwd", "DenseLMTrainer", self.cfg)

    def step(self, params, opt_state, tokens, labels):
        if self._step is None:
            self._build()
        lr, t = self._host_lr_t(params)
        return self._step(params, opt_state, tokens, labels, lr, t)

    def forward(self, params, tokens):
        if self._fwd is None:
            self._build()
        return self._fwd(params, tokens)


# ------------------------------------------------------------------- sp
class SPLMTrainer(_LMTrainerBase):
    """Sequence-parallel LM: activations sharded over T on the ``sp`` axis,
    ring attention over ICI. Batch replicated (combine with dp by adding a
    mesh axis and sharding B — the block code is axis-agnostic)."""

    def __init__(self, mesh, vocab_size, num_layers, model_dim, num_heads,
                 ffn_dim, seq_len, axis="sp", optimizer="sgd",
                 optimizer_params=None):
        super().__init__(optimizer, optimizer_params)
        self.mesh = mesh
        self.axis = axis
        self.cfg = dict(vocab_size=vocab_size, num_layers=num_layers,
                        model_dim=model_dim, num_heads=num_heads,
                        ffn_dim=ffn_dim, seq_len=seq_len)
        self._step = None
        self._fwd = None

    def init_params(self, seed=0):
        return init_lm_params(seed, **self.cfg)

    def _local_forward(self, p, tok_local):
        """Per-device body: tok_local (B, T/n) -> logits (B, T/n, V)."""
        import jax
        import jax.numpy as jnp

        from .ring import ring_attention_local

        axis, n = self.axis, self.mesh.shape[self.axis]
        cfg = self.cfg
        idx = jax.lax.axis_index(axis)
        t_loc = tok_local.shape[1]
        pos = p["pos_embed_weight"][0]  # (T, D)
        pos_local = jax.lax.dynamic_slice_in_dim(pos, idx * t_loc, t_loc, 0)
        x = p["embed_weight"][tok_local] + pos_local

        for i in range(cfg["num_layers"]):
            pre = "layer%d_" % i
            h = _ln(x, p[pre + "ln1_gamma"], p[pre + "ln1_beta"])
            q, k, v = _qkv(h, p[pre + "attn_in_weight"], cfg["num_heads"])
            attn = ring_attention_local(q, k, v, axis, n, causal=True)
            x = x + jnp.einsum("btd,de->bte", _merge_heads(attn),
                               p[pre + "attn_out_weight"], precision=_prec(x))
            h = _ln(x, p[pre + "ln2_gamma"], p[pre + "ln2_beta"])
            f = jax.nn.relu(jnp.einsum("btd,df->btf", h, p[pre + "ffn1_weight"], precision=_prec(h)))
            x = x + jnp.einsum("btf,fd->btd", f, p[pre + "ffn2_weight"], precision=_prec(f))
        x = _ln(x, p["final_ln_gamma"], p["final_ln_beta"])
        return jnp.einsum("btd,dv->btv", x, p["lm_head_weight"], precision=_prec(x))

    def _build(self):
        import jax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        tok_spec = P(None, axis)

        def loss_local(p, tok_local, lab_local):
            logits = self._local_forward(p, tok_local)
            # mean over local tokens, then mean of means == global mean
            # (equal shards); psum/axis-size keeps it exact and replicated
            local = _xent(logits, lab_local)
            return jax.lax.pmean(local, axis)

        pspec = {n: P() for n in lm_param_names(**self.cfg)}
        loss_fn = jax.shard_map(
            loss_local, mesh=self.mesh,
            in_specs=(pspec, tok_spec, tok_spec), out_specs=P(),
            check_vma=False,
        )

        def step(params, opt_state, tokens, labels, lr, t):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, tokens, labels))(params)
            params, opt_state = self._apply_updates(params, grads, opt_state, lr, t)
            return params, opt_state, loss

        self._step = _obs_jit(step, "lm.step", "SPLMTrainer",
                              self.cfg, donate_argnums=(0, 1))
        fwd_local = jax.shard_map(
            lambda p, tok: self._local_forward(p, tok),
            mesh=self.mesh, in_specs=(pspec, tok_spec),
            out_specs=P(None, axis, None), check_vma=False,
        )
        self._fwd = _obs_jit(fwd_local, "lm.fwd", "SPLMTrainer", self.cfg)

    def step(self, params, opt_state, tokens, labels):
        if self._step is None:
            self._build()
        lr, t = self._host_lr_t(params)
        return self._step(params, opt_state, tokens, labels, lr, t)

    def forward(self, params, tokens):
        if self._fwd is None:
            self._build()
        return self._fwd(params, tokens)


# ------------------------------------------------------------------- pp
class PPLMTrainer(_LMTrainerBase):
    """Pipeline-parallel LM: embedding + block stages over the ``pp`` axis
    via the heterogeneous pipeline_apply; the LM head runs replicated on the
    drained microbatch activations.

    Scope note: this trainer pipelines COMPUTE (GPipe microbatch schedule —
    each device executes only its stage), but parameters and optimizer state
    stay replicated on every device (pipeline_apply's heterogeneous mode
    ships each stage's pytree everywhere and devices read only their own).
    Use it to overlap stage compute, not to fit a model larger than one
    device's memory; for parameter sharding, use the homogeneous
    stacked-leaves mode of pipeline_apply (leaves sharded P('pp')) or
    SPMDTrainer param_rules."""

    def __init__(self, mesh, vocab_size, num_layers, model_dim, num_heads,
                 ffn_dim, seq_len, axis="pp", optimizer="sgd",
                 optimizer_params=None):
        super().__init__(optimizer, optimizer_params)
        S = mesh.shape[axis]
        if num_layers % S:
            raise ValueError(
                f"num_layers={num_layers} must divide over {S} pipeline stages"
            )
        self.mesh = mesh
        self.axis = axis
        self.cfg = dict(vocab_size=vocab_size, num_layers=num_layers,
                        model_dim=model_dim, num_heads=num_heads,
                        ffn_dim=ffn_dim, seq_len=seq_len)
        self._step = None
        self._fwd = None

    def init_params(self, seed=0):
        return init_lm_params(seed, **self.cfg)

    def _stages(self):
        """Split params into per-stage views + per-stage fns."""
        S = self.mesh.shape[self.axis]
        L = self.cfg["num_layers"]
        per = L // S
        heads = self.cfg["num_heads"]

        def embed_and_blocks(p, tok):
            import jax.numpy as jnp

            x = p["embed_weight"][tok.astype(jnp.int32)] + p["pos_embed_weight"][0]
            for i in range(per):
                x = _block_dense(p, "layer%d_" % i, x, heads)
            return x

        def blocks_only(first, p, x):
            for i in range(first, first + per):
                x = _block_dense(p, "layer%d_" % i, x, heads)
            return x

        fns = [embed_and_blocks]
        for s in range(1, S):
            fns.append(lambda p, x, _f=s * per: blocks_only(_f, p, x))
        return fns

    def _build(self):
        import jax
        import jax.numpy as jnp

        from .pipeline import pipeline_apply

        cfg = self.cfg
        S = self.mesh.shape[self.axis]
        fns = self._stages()

        def step(params, opt_state, tokens_mb, labels_mb, lr, t):
            # tokens_mb: (M, Bmb, T) int; labels same
            def loss_fn(p):
                stage_params = [p] * S  # views: each stage reads its own keys
                carry = (tokens_mb.shape[1], cfg["seq_len"], cfg["model_dim"])
                acts = pipeline_apply(
                    fns, stage_params, tokens_mb, self.mesh, axis=self.axis,
                    carry_shape=carry, carry_dtype=jnp.float32,
                )
                x = _ln(acts, p["final_ln_gamma"], p["final_ln_beta"])
                logits = jnp.einsum("mbtd,dv->mbtv", x, p["lm_head_weight"], precision=_prec(x))
                return _xent(logits, labels_mb)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state = self._apply_updates(params, grads, opt_state, lr, t)
            return params, opt_state, loss

        self._step = _obs_jit(step, "lm.step", "PPLMTrainer",
                              self.cfg, donate_argnums=(0, 1))

        def fwd(params, tokens_mb):
            stage_params = [params] * S
            carry = (tokens_mb.shape[1], cfg["seq_len"], cfg["model_dim"])
            acts = pipeline_apply(
                fns, stage_params, tokens_mb, self.mesh, axis=self.axis,
                carry_shape=carry, carry_dtype=jnp.float32,
            )
            x = _ln(acts, params["final_ln_gamma"], params["final_ln_beta"])
            return jnp.einsum("mbtd,dv->mbtv", x, params["lm_head_weight"], precision=_prec(x))

        self._fwd = _obs_jit(fwd, "lm.fwd", "PPLMTrainer", self.cfg)

    def step(self, params, opt_state, tokens_mb, labels_mb):
        if self._step is None:
            self._build()
        lr, t = self._host_lr_t(params)
        return self._step(params, opt_state, tokens_mb, labels_mb, lr, t)

    def forward(self, params, tokens_mb):
        if self._fwd is None:
            self._build()
        return self._fwd(params, tokens_mb)


# ------------------------------------------------------------------- ep
class MoELMTrainer(_LMTrainerBase):
    """Expert-parallel MoE LM: batch sharded over the ``ep`` axis, each
    block's FFN a Switch MoE whose experts live one-per-device-group, token
    routing via all_to_all (parallel/moe.py)."""

    def __init__(self, mesh, vocab_size, num_layers, model_dim, num_heads,
                 ffn_dim, seq_len, num_experts, axis="ep",
                 capacity_factor=2.0, optimizer="sgd", optimizer_params=None):
        super().__init__(optimizer, optimizer_params)
        n = mesh.shape[axis]
        if num_experts % n:
            raise ValueError(f"num_experts={num_experts} must divide {axis}={n}")
        self.mesh = mesh
        self.axis = axis
        self.capacity_factor = capacity_factor
        self.cfg = dict(vocab_size=vocab_size, num_layers=num_layers,
                        model_dim=model_dim, num_heads=num_heads,
                        ffn_dim=ffn_dim, seq_len=seq_len,
                        num_experts=num_experts)
        self._step = None
        self._fwd = None

    def init_params(self, seed=0):
        return init_lm_params(seed, **self.cfg)

    def _local_forward(self, p, tok_local):
        """Per-device body: tok_local (B/n, T) -> logits (B/n, T, V)."""
        import jax
        import jax.numpy as jnp

        from .moe import moe_ffn_local

        cfg = self.cfg
        axis, n = self.axis, self.mesh.shape[self.axis]
        B, T = tok_local.shape
        x = p["embed_weight"][tok_local] + p["pos_embed_weight"][0]
        for i in range(cfg["num_layers"]):
            pre = "layer%d_" % i
            h = _ln(x, p[pre + "ln1_gamma"], p[pre + "ln1_beta"])
            q, k, v = _qkv(h, p[pre + "attn_in_weight"], cfg["num_heads"])
            attn = _merge_heads(_dense_causal_attention(q, k, v))
            x = x + jnp.einsum("btd,de->bte", attn, p[pre + "attn_out_weight"], precision=_prec(x))
            h = _ln(x, p[pre + "ln2_gamma"], p[pre + "ln2_beta"])
            f = moe_ffn_local(
                h.reshape(B * T, cfg["model_dim"]),
                p[pre + "gate_weight"],
                p[pre + "ffn1_weight"], p[pre + "ffn2_weight"],
                axis, n, capacity_factor=self.capacity_factor,
            )
            x = x + f.reshape(B, T, cfg["model_dim"])
        x = _ln(x, p["final_ln_gamma"], p["final_ln_beta"])
        return jnp.einsum("btd,dv->btv", x, p["lm_head_weight"], precision=_prec(x))

    def _build(self):
        import jax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        tok_spec = P(axis)
        pspec = {
            n: (P(axis) if ("ffn1_weight" in n or "ffn2_weight" in n) else P())
            for n in lm_param_names(**self.cfg)
        }

        def loss_local(p, tok_local, lab_local):
            logits = self._local_forward(p, tok_local)
            return jax.lax.pmean(_xent(logits, lab_local), axis)

        loss_fn = jax.shard_map(
            loss_local, mesh=self.mesh,
            in_specs=(pspec, tok_spec, tok_spec), out_specs=P(),
            check_vma=False,
        )

        def step(params, opt_state, tokens, labels, lr, t):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, tokens, labels))(params)
            params, opt_state = self._apply_updates(params, grads, opt_state, lr, t)
            return params, opt_state, loss

        self._step = _obs_jit(step, "lm.step", "MoELMTrainer",
                              self.cfg, donate_argnums=(0, 1))
        self._fwd = _obs_jit(jax.shard_map(
            lambda p, tok: self._local_forward(p, tok),
            mesh=self.mesh, in_specs=(pspec, tok_spec),
            out_specs=P(axis, None, None), check_vma=False,
        ), "lm.fwd", "MoELMTrainer", self.cfg)

    def step(self, params, opt_state, tokens, labels):
        if self._step is None:
            self._build()
        lr, t = self._host_lr_t(params)
        return self._step(params, opt_state, tokens, labels, lr, t)

    def forward(self, params, tokens):
        if self._fwd is None:
            self._build()
        return self._fwd(params, tokens)
