#!/usr/bin/env python
"""Cluster launcher — spawn PS servers + workers for dist training.

Reference: tools/launch.py (dmlc-tracker submit: ssh/mpi/sge/yarn/local,
:13-60) setting the DMLC_* env contract consumed by ps-lite. The same
contract drives mxnet_tpu's native PS (kvstore.py KVStoreDist /
kvstore_server.py):

  DMLC_ROLE            worker | server | scheduler
  DMLC_PS_ROOT_URI     host of server 0
  DMLC_PS_ROOT_PORT    port of server 0 (server i listens on port+i)
  DMLC_NUM_WORKER / DMLC_NUM_SERVER
  DMLC_WORKER_ID / DMLC_SERVER_ID
  DMLC_PS_RECOVERY     set on relaunched workers (elastic mode)

Launchers: `local` (all processes on this host — the dev/test path) and
`ssh` (one process per host from a hostfile, reference dmlc-tracker ssh.py).
On TPU pods the *sync* data path needs no launcher at all (jax initializes
from the pod runtime); this launcher exists for dist_async / PS semantics
and CPU-host clusters.

`--elastic` (local launcher) turns the launcher into a supervisor
(docs/distributed.md §elasticity): every process runs with MXNET_ELASTIC=1,
and a worker that dies with a nonzero exit code is relaunched — with
DMLC_PS_RECOVERY=1, so it rejoins the running job through the PS membership
registry instead of re-initializing — up to MXNET_ELASTIC_MAX_RESTARTS
times per worker slot, with exponential backoff. Survivors keep training
through the loss (membership epochs + guard rollback); the job exits 0 once
every worker slot has completed. SERVER slots are supervised the same way
(docs/distributed.md §server-HA): a dead server is relaunched with
DMLC_PS_RECOVERY=1 so it restores its optimizer-slot checkpoint and rejoins
as a backup, while the registry promotes a replica to keep the key range
live in the meantime (MXNET_KV_REPLICAS).

Usage: python tools/launch.py -n 2 -s 1 python train_mnist.py --kv-store dist_sync
"""
import argparse
import os
import signal
import subprocess
import sys
import time


def run_local(args):
    base_env = _base_env(args)
    # the launcher must not import the framework (workers pay the jax
    # import; the supervisor stays a plain process babysitter)
    max_restarts = int(os.environ.get(  # fwlint: disable=env-raw-read — see above
        "MXNET_ELASTIC_MAX_RESTARTS", "3"))

    # elastic supervision exists to relaunch dead workers INTO a running
    # job — and a relaunch re-pays the full XLA compile wall unless the
    # compile cache persists across the incarnations. Default the cache
    # dir on, at the entry points' fixed path in the checkout
    # (compile_cache.DEFAULT_DIR, spelled out because the launcher must not
    # import the framework); a directory the environment names wins, and
    # an explicit empty MXNET_COMPILE_CACHE_DIR opts out.
    elastic_cache_dir = None
    if args.elastic and not any(k in os.environ for k in (
            "JAX_COMPILATION_CACHE_DIR", "MXNET_COMPILE_CACHE_DIR")):
        elastic_cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".compile_cache")

    def spawn(role, idx, recovery=False):
        env = dict(os.environ)
        env.update(base_env)
        env["DMLC_ROLE"] = role
        if args.elastic:
            env["MXNET_ELASTIC"] = "1"
            if elastic_cache_dir:
                env["MXNET_COMPILE_CACHE_DIR"] = elastic_cache_dir
            # a relaunched server is only useful if it can warm-start its
            # optimizer slots: default the server checkpoint cadence on
            # (docs/distributed.md §server-HA; explicit value wins, and an
            # explicit 0 opts out)
            if "MXNET_KV_SERVER_CKPT_STEPS" not in os.environ:
                env["MXNET_KV_SERVER_CKPT_STEPS"] = "32"
        if role == "server":
            env["DMLC_SERVER_ID"] = str(idx)
        else:
            env["DMLC_WORKER_ID"] = str(idx)
        # DMLC_PS_RECOVERY on a relaunched SERVER restores the slot
        # checkpoint (kvstore_server._restore_checkpoint); on a worker it
        # takes the elastic rejoin path instead of re-initializing
        if recovery:
            env["DMLC_PS_RECOVERY"] = "1"
        else:
            env.pop("DMLC_PS_RECOVERY", None)
        return subprocess.Popen(args.command, env=env)

    servers = {i: spawn("server", i) for i in range(args.num_servers)}
    workers = {i: spawn("worker", i) for i in range(args.num_workers)}
    done_ok = set()           # worker slots that exited 0
    restarts = {}             # worker slot -> relaunch count
    pending = {}              # worker slot -> monotonic relaunch time
    srv_restarts = {}         # server slot -> relaunch count
    srv_pending = {}          # server slot -> monotonic relaunch time
    state = {"sig": 0}

    def terminate_all():
        for p in list(workers.values()) + list(servers.values()):
            if p.poll() is None:
                p.terminate()

    def on_signal(signum, _frame):
        if state["sig"]:
            # second signal: the children were already told once — leave
            sys.exit(128 + signum)
        state["sig"] = signum
        terminate_all()  # forward exactly once

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)

    rc_final = None
    while rc_final is None:
        if state["sig"]:
            rc_final = 128 + state["sig"]
            break
        now = time.monotonic()
        # server slots are supervised exactly like worker slots under
        # --elastic (docs/distributed.md §server-HA): a dead server is
        # relaunched with backoff and DMLC_PS_RECOVERY=1 so it restores
        # its optimizer-slot checkpoint and rejoins as a backup — the
        # registry already promoted a replica meanwhile
        for i, when in list(srv_pending.items()):
            if now >= when:
                del srv_pending[i]
                print("launch.py: relaunching server %d (restart %d/%d)"
                      % (i, srv_restarts[i], max_restarts), file=sys.stderr)
                servers[i] = spawn("server", i, recovery=True)
        if args.elastic:
            for i, p in list(servers.items()):
                code = p.poll()
                if code is None:
                    continue
                del servers[i]
                if code == 0:
                    continue  # clean stop (rank 0's end-of-job shutdown)
                if not workers and not pending:
                    # job finishing: a relaunch would only rejoin a
                    # cluster that is shutting down
                    continue
                if srv_restarts.get(i, 0) >= max_restarts:
                    print("launch.py: server %d exceeded "
                          "MXNET_ELASTIC_MAX_RESTARTS=%d — terminating "
                          "the job" % (i, max_restarts), file=sys.stderr)
                    rc_final = code
                    break
                srv_restarts[i] = srv_restarts.get(i, 0) + 1
                delay = min(0.5 * (1 << (srv_restarts[i] - 1)), 30.0)
                print("launch.py: server %d died (code %d); relaunch in "
                      "%.1fs" % (i, code, delay), file=sys.stderr)
                srv_pending[i] = now + delay
            if rc_final is not None:
                break
        for i, when in list(pending.items()):
            if now >= when:
                del pending[i]
                print("launch.py: relaunching worker %d (restart %d/%d)"
                      % (i, restarts[i], max_restarts), file=sys.stderr)
                workers[i] = spawn("worker", i, recovery=True)
        for i, p in list(workers.items()):
            code = p.poll()
            if code is None:
                continue
            del workers[i]
            if code == 0:
                done_ok.add(i)
                continue
            if not args.elastic:
                # a dead worker wedges BSP rounds and barriers for everyone
                # else: kill the job NOW — servers included, they must not
                # linger to a reap timeout — and propagate the first failed
                # worker's exit code as the launcher's own
                print("launch.py: worker %d exited with code %d — "
                      "terminating the job" % (i, code), file=sys.stderr)
                rc_final = code
                break
            if args.num_workers > 1 and not workers and not pending \
                    and len(done_ok) == args.num_workers - 1:
                # every other slot completed: the job's work is done — a
                # relaunch would only rejoin a cluster that is shutting down
                print("launch.py: worker %d died (code %d) after all other "
                      "workers completed — not relaunching" % (i, code),
                      file=sys.stderr)
                rc_final = 0
                break
            if restarts.get(i, 0) >= max_restarts:
                print("launch.py: worker %d exceeded "
                      "MXNET_ELASTIC_MAX_RESTARTS=%d — terminating the job"
                      % (i, max_restarts), file=sys.stderr)
                rc_final = code
                break
            restarts[i] = restarts.get(i, 0) + 1
            delay = min(0.5 * (1 << (restarts[i] - 1)), 30.0)
            print("launch.py: worker %d died (code %d); relaunch in %.1fs"
                  % (i, code, delay), file=sys.stderr)
            pending[i] = now + delay
        if rc_final is None and not workers and not pending:
            rc_final = 0  # all worker slots completed
        time.sleep(0.1)

    if rc_final != 0:
        terminate_all()
    # workers done: servers were told to stop by worker rank 0; reap — on a
    # failure path they were just SIGTERMed and should go promptly
    for p in servers.values():
        try:
            p.wait(timeout=30 if rc_final == 0 else 5)
        except subprocess.TimeoutExpired:
            p.terminate()
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
    # reap any straggler worker (failure path)
    for p in workers.values():
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()
    sys.exit(rc_final)


def _base_env(args):
    return {
        "DMLC_PS_ROOT_URI": args.host,
        "DMLC_PS_ROOT_PORT": str(args.port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    }


def run_ssh(args):
    # ssh launcher (reference: dmlc-tracker ssh.py): hosts round-robin
    base_env = _base_env(args)
    assert args.hostfile, "--hostfile required for ssh launcher"
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    assert hosts, "empty hostfile"
    procs = []
    cwd = os.getcwd()
    if args.sync_dst_dir:
        for h in hosts:
            subprocess.run(["rsync", "-a", cwd + "/", "%s:%s/" % (h, args.sync_dst_dir)],
                           check=True)
        cwd = args.sync_dst_dir

    def ssh_spawn(host, role, idx):
        env = dict(base_env)
        env["DMLC_ROLE"] = role
        env["DMLC_SERVER_ID" if role == "server" else "DMLC_WORKER_ID"] = str(idx)
        envs = " ".join("%s=%s" % kv for kv in env.items())
        cmd = "cd %s && %s %s" % (cwd, envs, " ".join(args.command))
        return subprocess.Popen(["ssh", "-o", "StrictHostKeyChecking=no", host, cmd])

    # ALL servers run on --host: workers dial DMLC_PS_ROOT_URI:port+i for
    # every server i, so servers scattered across hosts would be unreachable
    for i in range(args.num_servers):
        procs.append(("server", ssh_spawn(args.host, "server", i)))
    for i in range(args.num_workers):
        procs.append(("worker", ssh_spawn(hosts[i % len(hosts)], "worker", i)))
    rc = 0
    for role, p in procs:
        if role == "worker":
            code = p.wait()
            if code != 0 and rc == 0:
                rc = code  # first failed worker's code, like the local path
    for role, p in procs:
        if role == "server":
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.terminate()
    sys.exit(rc)


def main():
    ap = argparse.ArgumentParser(description="Launch a dist training job")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=None,
                    help="default: same as workers")
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None,
                    help="ssh launcher: file with one host per line")
    ap.add_argument("--host", default="127.0.0.1", help="PS root host")
    ap.add_argument("--port", type=int, default=9091, help="PS root port")
    ap.add_argument("--sync-dst-dir", default=None,
                    help="ssh launcher: rsync working dir to hosts first")
    ap.add_argument("--elastic", action="store_true",
                    help="local launcher: supervise workers AND servers — "
                         "relaunch dead ones (MXNET_ELASTIC_MAX_RESTARTS, "
                         "backoff; servers restore their optimizer-slot "
                         "checkpoint) into the running job via the PS "
                         "membership registry")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if args.num_servers is None:
        args.num_servers = args.num_workers
    if args.launcher == "local":
        run_local(args)
    else:
        assert not args.elastic, "--elastic supports the local launcher only"
        run_ssh(args)


if __name__ == "__main__":
    main()
