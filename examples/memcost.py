"""Memory-for-compute demo (reference: example/memcost/ + the
MXNET_BACKWARD_DO_MIRROR recipe, docs env_var.md:64-66: inception-v3 went
from batch-64-at-10G to batch-128 by recomputing activations).

Trains one step of a deep MLP with and without activation recompute and
reports live-buffer peaks (from the device allocator when available, else the
XLA-reported compile-time peak).
"""
import argparse
import os
import subprocess
import sys


def run_child(mirror, depth, batch, hidden):
    env = dict(os.environ)
    env["MXNET_BACKWARD_DO_MIRROR"] = "1" if mirror else "0"
    code = r"""
import numpy as np
import jax
import mxnet_tpu as mx

depth, batch, hidden = %d, %d, %d
net = mx.sym.Variable("data")
for i in range(depth):
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc%%d" %% i)
    net = mx.sym.Activation(net, act_type="relu", name="relu%%d" %% i)
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(net, num_hidden=10, name="out"), name="softmax")
ex = net.simple_bind(ctx=mx.current_context(), data=(batch, hidden))
# compile-time plan: exact for a static graph. Note: XLA:CPU may elide the
# rematerialization (CSE) — run on a TPU to see the full savings.
ma = ex.memory_analysis()
peak = getattr(ma, "peak_memory_in_bytes", None)
if not peak:
    print("PEAK", -1)
else:
    print("PEAK", int(peak))
""" % (depth, batch, hidden)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    for line in out.stdout.splitlines():
        if line.startswith("PEAK"):
            return int(line.split()[1])
    return -1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=48)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=1024)
    args = ap.parse_args()

    plain = run_child(False, args.depth, args.batch, args.hidden)
    mirror = run_child(True, args.depth, args.batch, args.hidden)
    if plain < 0 or mirror < 0:
        print("device does not report memory stats; run on TPU for numbers")
        return
    print("peak bytes without mirror: %.1f MB" % (plain / 1e6))
    print("peak bytes with    mirror: %.1f MB" % (mirror / 1e6))
    print("saved: %.1f%%" % (100.0 * (plain - mirror) / max(plain, 1)))


if __name__ == "__main__":
    main()
