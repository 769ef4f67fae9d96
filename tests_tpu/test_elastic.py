"""Elastic multi-host training suite (docs/distributed.md §elasticity):
membership-epoch rejection on push AND pull, the PS membership registry
(formation / heartbeat lapse / rejoin), deterministic epoch-scoped
resharding through the iterator position protocol, the launcher's
supervisor + exit-code contract, and the full kill→reconfigure→rejoin
cycle on the multi-process CPU mesh (slow-marked).

Host-side only: runs on a CPU-only machine (tests_tpu/conftest.py exempts
this file from the hardware gate). `ci/run_tests.sh elastic` is the CI tier.
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.kvstore_server import (  # noqa: E402
    MembershipRegistry, decode_bytes_vec, encode_bytes_vec)
from mxnet_tpu._native import get_lib  # noqa: E402

pytestmark = pytest.mark.elastic

needs_native = pytest.mark.skipif(get_lib() is None,
                                  reason="native lib unavailable")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# wire codec for the registry's reserved-key publish channel
# ---------------------------------------------------------------------------

def test_bytes_vec_roundtrip():
    for payload in (b"", b"x", b'{"epoch": 3, "workers": [0, 2]}',
                    bytes(range(256))):
        vec = encode_bytes_vec(payload)
        assert vec.dtype == np.float32
        assert decode_bytes_vec(vec) == payload
        # a fixed-cap pull hands over a LONGER buffer: trailing zeros ignored
        padded = np.concatenate([vec, np.zeros(7, np.float32)])
        assert decode_bytes_vec(padded) == payload


def test_bytes_vec_rejects_torn_payload():
    vec = encode_bytes_vec(b"hello")
    assert decode_bytes_vec(vec[:3]) is None  # truncated below its length


# ---------------------------------------------------------------------------
# membership registry (in-process: broadcast injected)
# ---------------------------------------------------------------------------

def _registry(num_workers=2, timeout=0.3):
    sent = []
    reg = MembershipRegistry(num_workers, heartbeat_timeout_s=timeout,
                             broadcast=sent.append)
    return reg, sent


def test_registry_formation_keeps_epoch_zero():
    reg, sent = _registry()
    try:
        assert reg.join(0) == 0
        t = reg.table()
        assert not t["formed"] and t["epoch"] == 0
        assert reg.join(1) == 0
        t = reg.table()
        assert t["formed"] and t["epoch"] == 0 and t["workers"] == [0, 1]
        assert sent == []  # a normal start must not churn the servers
    finally:
        reg.close()


def test_registry_heartbeat_lapse_bumps_and_broadcasts():
    reg, sent = _registry(timeout=0.25)
    try:
        reg.join(0)
        reg.join(1)
        deadline = time.monotonic() + 5
        # keep 0 alive, let 1 lapse
        while reg.table()["epoch"] == 0 and time.monotonic() < deadline:
            reg.heartbeat(0)
            time.sleep(0.05)
        t = reg.table()
        assert t["epoch"] == 1 and t["workers"] == [0]
        assert sent == ["mepoch:1:1"]
        # a lapsed worker's late heartbeat must NOT resurrect it
        reg.heartbeat(1)
        assert reg.table()["workers"] == [0]
    finally:
        reg.close()


def test_registry_rejoin_of_live_rank_bumps():
    # a relaunched worker can rejoin FASTER than the lapse notices the old
    # incarnation died: the join itself must reconfigure (flush the old
    # incarnation's half-pushed rounds)
    reg, sent = _registry(timeout=60)
    try:
        reg.join(0)
        reg.join(1)
        reg.join(1)  # rank 1 again, while still listed alive
        t = reg.table()
        assert t["epoch"] == 1 and t["workers"] == [0, 1]
        assert sent == ["mepoch:1:2"]
    finally:
        reg.close()


def test_registry_pos_published_and_cleared_on_bump():
    reg, sent = _registry(timeout=60)
    try:
        reg.join(0)
        reg.join(1)
        reg.set_pos({"mepoch": 0, "epoch": 2, "nbatch": 5})
        assert reg.table()["pos"]["nbatch"] == 5
        reg.leave(1)  # bump -> the old membership's position is stale
        t = reg.table()
        assert t["epoch"] == 1 and t["pos"] is None
        assert sent == ["mepoch:1:1"]
    finally:
        reg.close()


def test_registry_done_only_exempts_reported_ranks():
    reg, sent = _registry(timeout=0.25)
    try:
        reg.join(0)
        reg.join(1)
        reg.done(0)
        t = reg.table()
        assert t["done"] and 0 not in t["workers"]
        # rank 0 reported done: silent forever, never lapses. rank 1 did
        # NOT — keep it beating: no bump may fire while it is healthy...
        deadline = time.monotonic() + 0.7
        while time.monotonic() < deadline:
            reg.heartbeat(1)
            time.sleep(0.05)
        assert reg.table()["epoch"] == 0 and sent == []
        # ...but a rank killed before reporting done must still lapse, or
        # a finished peer's trailing barrier would wait on it forever
        deadline = time.monotonic() + 5
        while reg.table()["epoch"] == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert reg.table()["epoch"] == 1 and sent == ["mepoch:1:1"]
    finally:
        reg.close()


# ---------------------------------------------------------------------------
# deterministic epoch-scoped resharding (iterator position protocol)
# ---------------------------------------------------------------------------

def _batch_sums(it, n=None):
    out = []
    for batch in it:
        out.append(float(np.abs(batch.data[0].asnumpy()).sum()))
        if n is not None and len(out) == n:
            break
    return out


def test_ndarrayiter_partition_args_slice_contiguously():
    X = np.arange(40, dtype=np.float32).reshape(40, 1)
    full = mx.io.NDArrayIter(X, np.zeros(40, np.float32), batch_size=5)
    p0 = mx.io.NDArrayIter(X, np.zeros(40, np.float32), batch_size=5,
                           num_parts=2, part_index=0)
    p1 = mx.io.NDArrayIter(X, np.zeros(40, np.float32), batch_size=5,
                           num_parts=2, part_index=1)
    assert p0.num_data == p1.num_data == 20
    assert _batch_sums(full) == _batch_sums(p0) + _batch_sums(p1)


def test_ndarrayiter_set_partition_same_stream_as_fresh_iter():
    rng = np.random.RandomState(3)
    X = rng.randn(64, 4).astype(np.float32)
    y = np.zeros(64, np.float32)
    # reference: an iterator BORN on shard (2, 1)
    fresh = mx.io.NDArrayIter(X, y, batch_size=8, num_parts=2, part_index=1)
    expected = _batch_sums(fresh)
    # an iterator that trained on shard (2, 0), then resharded mid-job
    it = mx.io.NDArrayIter(X, y, batch_size=8, num_parts=2, part_index=0)
    it.next()
    it.next()
    it.set_partition(2, 1)
    it.reset()
    assert _batch_sums(it) == expected
    # ...and the position protocol fast-forwards within the NEW shard
    # (after n delivered batches the cursor sits at (n-1)*batch_size —
    # the next iter_next() advances onto batch n)
    it.set_partition(2, 1)
    it.load_state({"type": "NDArrayIter", "cursor": 1 * 8})
    assert _batch_sums(it) == expected[2:]


def test_ndarrayiter_seeded_shuffle_is_reproducible_across_reshards():
    X = np.arange(48, dtype=np.float32).reshape(48, 1)
    y = np.zeros(48, np.float32)
    a = mx.io.NDArrayIter(X, y, batch_size=4, shuffle=True, seed=11,
                          num_parts=2, part_index=0)
    b = mx.io.NDArrayIter(X, y, batch_size=4, shuffle=True, seed=11,
                          num_parts=3, part_index=2)
    b.set_partition(2, 0)  # reshard lands on a's exact stream
    assert _batch_sums(a) == _batch_sums(b)


def test_ndarrayiter_unseeded_shuffle_refuses_reshard():
    it = mx.io.NDArrayIter(np.zeros((16, 2), np.float32),
                           np.zeros(16, np.float32), batch_size=4,
                           shuffle=True)
    with pytest.raises(MXNetError, match="seed"):
        it.set_partition(2, 0)


@pytest.fixture(scope="module")
def small_rec(tmp_path_factory):
    from rec_fixtures import gen_dataset, pack

    workdir = str(tmp_path_factory.mktemp("rec"))
    img_dir, lst = gen_dataset(workdir, n=24, size=32)
    return pack(workdir, img_dir, lst)


def test_imagerecorditer_set_partition_fast_forward(small_rec):
    kw = dict(path_imgrec=small_rec, data_shape=(3, 32, 32), batch_size=4,
              preprocess_threads=1, seed=7)
    # reference stream: an iterator BORN on shard (2, 1)
    born = mx.io_image.ImageRecordIter(num_parts=2, part_index=1, **kw)
    try:
        expected = _batch_sums(born)
    finally:
        born.close()
    assert len(expected) == 3  # 24 records / 2 parts / batch 4
    # a full-stream iterator resharded mid-epoch, then fast-forwarded one
    # batch via the position protocol: exactly the reference's suffix
    it = mx.io_image.ImageRecordIter(**kw)
    try:
        it.next()
        it.set_partition(2, 1)
        it.load_state({"type": "ImageRecordIter", "epoch": 0, "batches": 1})
        assert _batch_sums(it) == pytest.approx(expected[1:])
    finally:
        it.close()


# ---------------------------------------------------------------------------
# membership-epoch rejection: stale traffic cannot land (push AND pull)
# ---------------------------------------------------------------------------

WORKER_STALE_EPOCH = r"""
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.kvstore import KVMembershipError

kv = mx.kv.create("dist_sync")
kv.elastic_enable()
kv.init(0, mx.nd.ones((4,)))
# the registry normally drives this; bump the server's epoch directly so
# THIS worker is provably stale
assert kv._lib.mxt_ps_client_command(kv._clients[0], b"mepoch:5:1") == 0

def rejected(op):
    return telemetry.counter("kv.membership.rejected", op=op).value

base_push, base_pull = rejected("push"), rejected("pull")
try:
    kv._zpush(0, np.ones(4, np.float32))
    raise SystemExit("stale push was accepted")
except KVMembershipError as e:
    assert e.op == "push", e.op
try:
    kv._zpull(0, 4)
    raise SystemExit("stale pull was accepted")
except KVMembershipError as e:
    assert e.op == "pull", e.op
assert rejected("push") == base_push + 1
assert rejected("pull") == base_pull + 1
# pull the value through a FRESH read after adoption: the stale push above
# must not have mutated server state
kv.set_membership_epoch(5)
out = mx.nd.zeros((4,))
kv.pull(0, out=out)
assert np.allclose(out.asnumpy(), 1.0), out.asnumpy()
# adopted-epoch traffic flows: push applies now
kv.push(0, mx.nd.ones((4,)) * 3)
kv.pull(0, out=out)
assert np.allclose(out.asnumpy(), 3.0), out.asnumpy()
kv.barrier()
kv._stop_servers()
print("WORKER_OK")
"""


def _run_cluster(script, n_workers=1, env_extra=None, timeout=180,
                 launch_args=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DMLC_ROLE", None)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
           "-n", str(n_workers), "-s", "1", "--port", str(_free_port()),
           *launch_args, sys.executable, "-c", script]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        out, err = proc.communicate()
        raise AssertionError("cluster hung: %s %s" % (out, err))
    return proc.returncode, out, err


@needs_native
def test_stale_epoch_rejected_on_push_and_pull():
    rc, out, err = _run_cluster(WORKER_STALE_EPOCH)
    assert rc == 0, (out, err)
    assert "WORKER_OK" in out, (out, err)


WORKER_STALE_BARRIER = r"""
import mxnet_tpu as mx
from mxnet_tpu.kvstore import KVMembershipError

kv = mx.kv.create("dist_sync")
kv.elastic_enable()
kv.init(0, mx.nd.ones((2,)))
assert kv._lib.mxt_ps_client_command(kv._clients[0], b"mepoch:9:1") == 0
try:
    kv.barrier()
    raise SystemExit("stale barrier was accepted")
except KVMembershipError:
    pass
kv.set_membership_epoch(9)
kv.barrier()
kv._stop_servers()
print("WORKER_OK")
"""


@needs_native
def test_stale_epoch_rejected_on_barrier():
    rc, out, err = _run_cluster(WORKER_STALE_BARRIER)
    assert rc == 0, (out, err)
    assert "WORKER_OK" in out, (out, err)


# ---------------------------------------------------------------------------
# launcher contract (non-elastic satellite + elastic supervisor)
# ---------------------------------------------------------------------------

FAIL_FAST_SCRIPT = (
    "import os, sys, time\n"
    "if os.environ['DMLC_ROLE'] != 'worker':\n"
    "    time.sleep(60)\n"  # a server that would linger to a reap timeout
    "if os.environ['DMLC_WORKER_ID'] == '1':\n"
    "    sys.exit(7)\n"
    "time.sleep(60)\n"
)


def test_launch_propagates_first_failed_worker_exit_code():
    t0 = time.monotonic()
    rc, out, err = _run_cluster(FAIL_FAST_SCRIPT, n_workers=2, timeout=60)
    took = time.monotonic() - t0
    # the failed worker's OWN code, not a bitwise-OR mash; and the group —
    # servers included — was SIGTERMed promptly, not reaped by timeout
    assert rc == 7, (rc, out, err)
    assert took < 30, "launcher waited on lingering processes (%.1fs)" % took


def test_launch_forwards_signal_once_and_exits():
    env = dict(os.environ)
    env.pop("DMLC_ROLE", None)
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
           "-n", "1", "-s", "1", "--port", str(_free_port()),
           sys.executable, "-c", "import time; time.sleep(60)"]
    proc = subprocess.Popen(cmd, env=env, start_new_session=True)
    time.sleep(2.0)  # children spawned
    os.kill(proc.pid, signal.SIGTERM)
    try:
        rc = proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        raise AssertionError("launcher ignored SIGTERM")
    assert rc == 128 + signal.SIGTERM


def test_elastic_sets_default_compile_cache_dir():
    """--elastic defaults MXNET_COMPILE_CACHE_DIR for every child (a
    relaunch must start warm — docs/compiler.md); an explicit value (or
    explicit empty = opt-out) wins over the default."""
    script = ("import os; print('CACHE_DIR=%s' % "
              "os.environ.get('MXNET_COMPILE_CACHE_DIR', ''))")
    rc, out, err = _run_cluster(script, n_workers=1, timeout=60,
                                launch_args=("--elastic",))
    assert rc == 0, (rc, out, err)
    line = [l for l in out.splitlines() if l.startswith("CACHE_DIR=")][0]
    # one fixed path inside the checkout — never tempfile, a pid or the time
    assert line == "CACHE_DIR=" + os.path.join(ROOT, ".compile_cache"), out
    # explicit value wins
    rc, out, err = _run_cluster(
        script, n_workers=1, timeout=60,
        env_extra={"MXNET_COMPILE_CACHE_DIR": "/tmp/explicit-cc"},
        launch_args=("--elastic",))
    assert rc == 0, (rc, out, err)
    assert "CACHE_DIR=/tmp/explicit-cc" in out, out


def test_non_elastic_leaves_compile_cache_unset():
    script = ("import os; print('CACHE_DIR=%s' % "
              "os.environ.get('MXNET_COMPILE_CACHE_DIR', 'UNSET'))")
    rc, out, err = _run_cluster(script, n_workers=1, timeout=60)
    assert rc == 0, (rc, out, err)
    assert "CACHE_DIR=UNSET" in out, out


def test_elastic_worker_exceeding_restart_budget_fails_job():
    script = "import sys; sys.exit(3)"  # every incarnation dies at once
    t0 = time.monotonic()
    rc, out, err = _run_cluster(
        script, n_workers=1, timeout=120,
        env_extra={"MXNET_ELASTIC_MAX_RESTARTS": "2"},
        launch_args=("--elastic",))
    assert rc == 3, (rc, out, err)
    assert err.count("relaunching worker 0") == 2, err
    assert "exceeded MXNET_ELASTIC_MAX_RESTARTS" in err, err
    assert time.monotonic() - t0 < 60


# ---------------------------------------------------------------------------
# the whole cycle: kill mid-epoch -> survivors reconfigure -> relaunch
# rejoins -> deterministic resharded stream + identical final params
# ---------------------------------------------------------------------------

ELASTIC_FIT = r"""
import os

# the kill rule targets THIS rank's first incarnation only: a relaunched
# process starts with fresh fault counters and must not re-kill itself
if os.environ.get("DMLC_PS_RECOVERY"):
    os.environ.pop("MXNET_FAULT_SPEC", None)

import numpy as np
import mxnet_tpu as mx

seed = 42
rng = np.random.RandomState(seed)
X = rng.randn(256, 10).astype(np.float32)
w_true = rng.randn(10, 1).astype(np.float32)
y = (X @ w_true > 0).astype(np.float32).reshape(-1)

np.random.seed(seed)  # initializer determinism across workers/incarnations

kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
# the FULL dataset + partition args: the elastic reshard re-slices the
# original arrays when the membership changes
it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=False,
                       num_parts=nw, part_index=rank)

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())

stream = []  # (epoch, checksum) for every trained batch


def record(param):
    import time

    b = param.locals["data_batch"]
    stream.append((param.epoch,
                   float(np.abs(b.data[0].asnumpy()).sum())))
    # pace the loop: the surviving worker must still be training when the
    # relaunched one (a fresh python + jax import away) rejoins
    time.sleep(0.1)


NUM_EPOCH = 10
mod.fit(it, num_epoch=NUM_EPOCH, kvstore=kv, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1},
        initializer=mx.init.Xavier(rnd_type="gaussian", magnitude=2.0),
        eval_metric="acc", force_init=True, batch_end_callback=record)

arg, _ = mod.get_params()
sig = float(sum(float(np.abs(v.asnumpy()).sum()) for v in arg.values()))
last = [c for e, c in stream if e == NUM_EPOCH - 1][-8:]
from mxnet_tpu import compileobs
cs = compileobs.summary(include_recompiles=False)
os.write(1, ("ELASTIC_DONE rank=%d recovered=%s sig=%.4f cmpl=%.3f "
             "cold=%d last=%s\n"
             % (rank, os.environ.get("DMLC_PS_RECOVERY", "0"), sig,
                cs["compile_seconds"], int(cs.get("cache_misses", -1)),
                ",".join("%.3f" % c for c in last))).encode())
kv.barrier()
if rank == 0:
    kv._stop_servers()
print("WORKER_OK", rank)
"""


@needs_native
@pytest.mark.slow
def test_elastic_kill_rejoin_end_to_end(tmp_path):
    """Acceptance scenario: fault.py SIGKILLs worker 1 mid-epoch under
    ``launch.py --elastic``; the survivor reconfigures (epoch bump, reshard,
    guard rollback) instead of dying, the launcher relaunches the worker,
    it rejoins through the registry, and the job completes with final
    params BIT-IDENTICAL across workers and a post-reconfiguration batch
    stream that is exactly the pure function of (seed, partition,
    position) the iterator-position protocol promises. The relaunched
    incarnation also starts WARM off the persistent compile cache its
    first launch populated: its compile seconds must drop well below the
    cold worker's (docs/compiler.md)."""
    rc, out, err = _run_cluster(
        ELASTIC_FIT, n_workers=2, timeout=420,
        env_extra={
            # kill rank 1's first incarnation 20 batches in (mid-epoch 2:
            # 8 batches/epoch/worker), then never again
            "MXNET_FAULT_SPEC": "kill_worker:rank=1,after=20,times=1",
            "MXNET_ELASTIC_HEARTBEAT_S": "0.5",
            "MXNET_ELASTIC_HEARTBEAT_TIMEOUT_S": "2",
            # a per-test cache dir: the first incarnations start cold by
            # construction, the relaunch finds a populated cache
            "MXNET_COMPILE_CACHE_DIR": str(tmp_path / "cc"),
        },
        launch_args=("--elastic",))
    assert rc == 0, (rc, out, err)
    assert out.count("WORKER_OK") == 2, (out, err)
    lines = [l for l in out.splitlines() if l.startswith("ELASTIC_DONE")]
    assert len(lines) == 2, (out, err)
    info = {}
    for l in lines:
        kvs = dict(f.split("=", 1) for f in l.split()[1:])
        info[int(kvs["rank"])] = kvs
    # the dead worker really was relaunched into the job
    assert info[1]["recovered"] == "1", (out, err)
    assert info[0]["recovered"] == "0", (out, err)
    # warm restart: the relaunched incarnation compiled against the cache
    # its first launch (and rank 0) populated — its compile wall must be a
    # fraction of the cold worker's (the tentpole's elastic payoff)
    cold_s = float(info[0]["cmpl"])
    warm_s = float(info[1]["cmpl"])
    assert warm_s < 0.6 * cold_s, info
    # the full cycle is visible: reconfiguration AND rejoin happened
    assert "elastic: reconfigured to membership epoch" in err, err
    assert "elastic: joined membership epoch" in err, err
    # BSP held through the reconfigurations: identical final params
    assert info[0]["sig"] == info[1]["sig"], info
    # deterministic reshard: after the final reconfiguration both workers
    # run shard (2, rank) of the ORIGINAL arrays — their last batches must
    # equal the stream a from-scratch iterator on that shard yields
    rng = np.random.RandomState(42)
    X = rng.randn(256, 10).astype(np.float32)
    for rank in (0, 1):
        shard = X[rank * 128:(rank + 1) * 128]
        expect = [float(np.abs(shard[k * 16:(k + 1) * 16]).sum())
                  for k in range(8)]
        got = [float(v) for v in info[rank]["last"].split(",")]
        # the final epoch always runs its full 8 batches on shard (2, rank)
        # — even a reconfiguration landing inside it restarts the epoch
        # from batch 0, so the LAST 8 recorded batches are the whole epoch
        assert len(got) == 8, info
        np.testing.assert_allclose(got, expect, rtol=0, atol=2e-3)
