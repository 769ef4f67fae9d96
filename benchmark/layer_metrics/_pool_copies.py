"""The one pool-copy reader: share of device busy time the serving programs
spend copying the whole KV pool. ``decode_pool_copy_share`` and
``prefill_pool_copy_share`` are two names of ``share``, one per end-to-end
metric it moves (the pattern of ``_idle.py``).

What is counted: ops named ``copy*`` inside ``jit__decode`` and
``jit__prefill`` whose printed shape begins with the configuration's
``num_layers, num_blocks`` — ``jit__decode/copy.8 f32[24,513,16,16,64]`` is
one change of layout of the pool on the way into or out of a program. A
pool whose rows fill the 128 lanes has one layout and the share reads 0
(PERF.md, PR 25); a smaller copy (a block's copy-on-write, a q row) has
another shape and is not counted.
"""
from benchmark.layer_metrics._kernels import PROGRAM

PROGRAMS = tuple(name + "/" for name in PROGRAM.values())


def share(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    ops = {name: s for name, s in tr["op_seconds"].items()
           if name.startswith(PROGRAMS)}
    if not ops:
        return None     # no serving program in the trace: nothing to read
    cfg = obs["config"]
    pool = "[%d,%d," % (cfg["model"]["num_layers"],
                        cfg["engine"]["num_blocks"])
    copies = sum(s for name, s in ops.items()
                 if name.split("/", 1)[1].startswith("copy")
                 and pool in name)
    return 100.0 * copies / tr["busy_s"]
