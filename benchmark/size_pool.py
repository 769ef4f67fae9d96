#!/usr/bin/env python3
"""Settle a serving configuration's KV pool ONCE (a copy of chip_smoke's
``size_pool`` ladder): the largest rung whose decode program at
``max_batch`` — compiled here for its ``memory_analysis()`` — plus the pool
fits the budget. The answer is written into the configuration file as a
number by hand; a run does not repeat the ladder.

    python3 benchmark/size_pool.py benchmark/configs/gpt2-medium-fp32.json
        on the chip: budget = 0.8 x the device's bytes_limit
    JAX_PLATFORMS=cpu python3 benchmark/size_pool.py <config> --described
        in the sandbox: compiles for a DESCRIBED v5e (a compiler's plan,
        never a measurement); budget = 0.8 x 15.75 GiB
"""
import argparse
import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

POOL_LADDER = (2049, 1025, 513, 257)
V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--described", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.serving import model as lm

    with open(args.config) as f:
        cfg = json.load(f)
    m, e = cfg["model"], cfg["engine"]
    mcfg = lm.ModelConfig(m["vocab"], m["num_layers"], m["model_dim"],
                          m["num_heads"], m["ffn_dim"], m["max_len"])
    if args.described:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = dict(sharding=SingleDeviceSharding(topo.devices[0]))
        limit = V5E_BYTES_LIMIT
    else:
        where = {}
        limit = jax.devices()[0].memory_stats()["bytes_limit"]
    budget = int(0.8 * limit)
    spec = functools.partial(jax.ShapeDtypeStruct, **where)
    bs, B = e["block_size"], e["max_batch"]
    heads, hd = mcfg.num_heads, mcfg.model_dim // mcfg.num_heads
    kv = jnp.dtype(e["kv_dtype"])
    params = {k: spec(v, jnp.dtype(cfg["weights_dtype"]))
              for k, v in lm.param_shapes(mcfg).items()}
    ints = spec((B,), jnp.int32)
    tables = spec((B, mcfg.max_len // bs), jnp.int32)
    for n in POOL_LADDER:
        pages = spec((mcfg.num_layers, n, bs, heads, hd), kv)
        pool = 2 * int(np.prod(pages.shape)) * kv.itemsize
        try:
            ma = jax.jit(functools.partial(lm.decode, cfg=mcfg),
                         donate_argnums=(5, 6)).lower(
                params, ints, ints, tables, ints, pages, pages
            ).compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            # the compiler itself refuses a program that cannot fit
            print(json.dumps(dict(
                num_blocks=n, pool_bytes=pool, fits=False,
                refused=str(e).splitlines()[0][:200])), flush=True)
            continue
        # arguments hold the weights and the pool; the pool is donated
        need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        row = dict(num_blocks=n, pool_bytes=pool,
                   decode_temp_bytes=ma.temp_size_in_bytes,
                   decode_argument_bytes=ma.argument_size_in_bytes,
                   need_bytes=need, budget_bytes=budget,
                   fits=need <= budget)
        print(json.dumps(row), flush=True)
        if need <= budget:
            return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
