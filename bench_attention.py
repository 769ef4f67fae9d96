"""Flash-attention kernel throughput (backs the numbers in
docs/long_context.md): Pallas kernel vs XLA scan lowering vs the jax library
flash kernel, bf16, causal, batch 4 x 8 heads x seq 4096 x head_dim 64.

Prints one JSON line per variant: {"variant", "ms", "tflops"}.
Methodology matches bench.py: dispatch a pipelined loop, force completion with
one scalar fetch, report amortized time.
"""
import json
import os
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as A

    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "4"))
    H = int(os.environ.get("MXNET_TPU_BENCH_HEADS", "8"))
    T = int(os.environ.get("MXNET_TPU_BENCH_SEQ", "4096"))
    D = int(os.environ.get("MXNET_TPU_BENCH_HEAD_DIM", "64"))
    steps = int(os.environ.get("MXNET_TPU_BENCH_STEPS", "50"))
    rng = np.random.RandomState(0)
    q = jax.device_put((rng.rand(B, H, T, D) * 0.1).astype(jnp.bfloat16))
    flops = 4 * B * H * T * T * D / 2  # causal half

    def bench(fn):
        out = fn()
        float(np.asarray(jnp.sum(out)))  # warm + compile
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn()
        float(np.asarray(jnp.sum(out)))  # completion barrier
        return (time.perf_counter() - t0) / steps

    scale = float(1.0 / np.sqrt(D))
    k_fwd = jax.device_put((rng.rand(B, H, T, D) * 0.1).astype(jnp.bfloat16))
    v_fwd = jax.device_put((rng.rand(B, H, T, D) * 0.1).astype(jnp.bfloat16))
    variants = {
        "pallas_flash": jax.jit(lambda a, b, c: A._pallas_forward(a, b, c, True, scale)[0]),
        "xla_scan": jax.jit(lambda a, b, c: A._scan_forward(a, b, c, True, scale, 256)[0]),
    }
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash,
        )

        variants["jax_library_flash"] = jax.jit(
            lambda a, b, c: jax_flash(a, b, c, causal=True, sm_scale=scale))
    except ImportError:
        pass

    # backward pass variants (training is bwd-dominated). Distinct q/k/v/g
    # arrays passed as ARGUMENTS — same-array closure inputs let XLA CSE the
    # recompute matmuls and overstate throughput.
    k_in = jax.device_put((rng.rand(B, H, T, D) * 0.1).astype(jnp.bfloat16))
    v_in = jax.device_put((rng.rand(B, H, T, D) * 0.1).astype(jnp.bfloat16))
    g_in = jax.device_put((rng.rand(B, H, T, D) * 0.1).astype(jnp.bfloat16))
    out, lse = jax.jit(lambda a, b, c: A._pallas_forward(a, b, c, True, scale))(q, k_in, v_in)
    bflops = flops * 2.5
    # reduce over ALL THREE grads: returning only dq would let XLA dead-code-
    # eliminate the dk/dv computation and overstate throughput ~2x
    def _total(grads):
        return sum(jnp.sum(t.astype(jnp.float32)) for t in grads)

    bwd = {
        "pallas_backward": jax.jit(
            lambda a, b, c, o, l, gg: _total(A._pallas_backward(a, b, c, o, l, gg, True, scale))),
        "scan_backward": jax.jit(
            lambda a, b, c, o, l, gg: _total(A._scan_backward(a, b, c, o, l, gg, True, scale, 256))),
    }
    for name, f in bwd.items():
        dt = bench(lambda: f(q, k_in, v_in, out, lse, g_in))
        print(json.dumps({"variant": name, "seq": T, "head_dim": D,
                          "ms": round(dt * 1e3, 2),
                          "tflops": round(bflops / dt / 1e12, 1)}), flush=True)

    for name, fn in variants.items():
        dt = bench(lambda: fn(q, k_fwd, v_fwd))
        print(json.dumps({
            "variant": name, "seq": T, "head_dim": D,
            "ms": round(dt * 1e3, 2),
            "tflops": round(flops / dt / 1e12, 1),
        }), flush=True)


if __name__ == "__main__":
    main()
