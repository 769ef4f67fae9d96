"""Runtime-telemetry suite: registry semantics under concurrent writers,
Prometheus / chrome-trace exposition, fit-loop step metrics, and the KV
retry counters under deterministic fault injection.

Host-side only: runs on a CPU-only machine (tests_tpu/conftest.py exempts
this file from the hardware gate). `ci/run_tests.sh telemetry` is the CI
tier.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import fault  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu._native import get_lib  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402

pytestmark = pytest.mark.telemetry

needs_native = pytest.mark.skipif(get_lib() is None,
                                  reason="native lib unavailable")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test sees a fresh, enabled registry and leaves it disabled."""
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.stop_flusher(final_flush=False)
    telemetry.disable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# instrument semantics
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    c = telemetry.counter("t.counter")
    c.inc()
    c.inc(41)
    assert c.value == 42
    with pytest.raises(ValueError):
        c.inc(-1)
    g = telemetry.gauge("t.gauge")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.value == 3.0
    # identity: same name+labels -> same object; labels split instruments
    assert telemetry.counter("t.counter") is c
    assert telemetry.counter("t.counter", op="x") is not c
    # a name registered as one kind cannot silently become another — even
    # under a different label set (the Prometheus one-type-per-name rule;
    # a mixed-type name would crash the scrape endpoint otherwise)
    with pytest.raises(TypeError):
        telemetry.gauge("t.counter")
    with pytest.raises(TypeError):
        telemetry.histogram("t.counter", key="3")
    telemetry.prometheus_text()  # still renders after the rejected attempts


def test_histogram_percentiles_and_bounds():
    h = telemetry.histogram("t.hist")
    assert h.percentile(50) is None  # empty
    for v in [0.001] * 50 + [0.01] * 45 + [5.0] * 5:
        h.observe(v)
    assert h.count == 100
    assert abs(h.sum - (0.05 + 0.45 + 25.0)) < 1e-9
    p50, p95, p99 = h.percentile(50), h.percentile(95), h.percentile(99)
    assert p50 <= p95 <= p99
    assert p50 <= 0.0025  # the p50 mass sits in the ~1ms bucket
    assert p99 >= 2.5     # the tail lands in the 5s observations' bucket
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["min"] == 0.001 and snap["max"] == 5.0
    assert snap["buckets"]["+Inf"] == 100
    # bounded: bucket array never grows with observations
    assert len(snap["buckets"]) == len(telemetry.DEFAULT_BUCKETS) + 1


def test_concurrent_writers_lose_nothing():
    c = telemetry.counter("t.conc.counter")
    g = telemetry.gauge("t.conc.gauge")
    h = telemetry.histogram("t.conc.hist")
    n_threads, n_iter = 8, 2000

    def work(seed):
        for i in range(n_iter):
            c.inc()
            g.set(i)
            h.observe((seed + i) % 7 * 0.001)

    threads = [threading.Thread(target=work, args=(s,))
               for s in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    snap = h.snapshot()
    assert snap["buckets"]["+Inf"] == n_threads * n_iter


def test_timer_context_observes():
    h = telemetry.histogram("t.timer")
    with h.time():
        time.sleep(0.002)
    assert h.count == 1
    assert h.sum >= 0.002


# ---------------------------------------------------------------------------
# exposition: JSON dump + Prometheus text
# ---------------------------------------------------------------------------


def test_dump_is_json_serializable_and_complete():
    telemetry.counter("d.counter", op="push").inc(3)
    telemetry.gauge("d.gauge").set(1.5)
    telemetry.histogram("d.hist").observe(0.01)
    telemetry.event("d.event", epoch=2)
    d = json.loads(json.dumps(telemetry.dump()))
    assert d["counters"]["d.counter{op=push}"] == 3
    assert d["gauges"]["d.gauge"] == 1.5
    assert d["histograms"]["d.hist"]["count"] == 1
    assert d["events"][-1]["event"] == "d.event"
    assert d["events"][-1]["epoch"] == 2


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                   # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""        # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"   # more labels
    r" (\+Inf|-Inf|NaN|[0-9eE.+-]+)$")             # value


def test_prometheus_text_parses():
    telemetry.counter("p.counter", op="pull").inc(7)
    telemetry.gauge("p.gauge").set(0.25)
    h = telemetry.histogram("p.hist")
    for v in (0.001, 0.2, 40.0):
        h.observe(v)
    text = telemetry.prometheus_text()
    types = {}
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram")
            types[name] = kind
        elif line.startswith("# HELP "):
            # cataloged metrics registered by OTHER tests in the same
            # process (e.g. compileobs gauges) legitimately carry free-text
            # HELP lines — this test only checks the sample format
            continue
        else:
            assert _PROM_LINE.match(line), "unparseable line: %r" % line
            name, _, value = line.rpartition(" ")
            samples[name] = value
    assert types["mxnet_p_counter"] == "counter"
    assert samples['mxnet_p_counter{op="pull"}'] == "7"
    assert float(samples["mxnet_p_gauge"]) == 0.25
    # histogram triplet with cumulative, monotone buckets ending at +Inf
    assert samples["mxnet_p_hist_count"] == "3"
    assert float(samples["mxnet_p_hist_sum"]) == pytest.approx(40.201)
    buckets = [(k, int(v)) for k, v in samples.items()
               if k.startswith("mxnet_p_hist_bucket")]
    counts = [v for _, v in buckets]
    assert counts == sorted(counts), "buckets must be cumulative"
    assert buckets[-1][0].endswith('le="+Inf"}') and buckets[-1][1] == 3


# ---------------------------------------------------------------------------
# spans -> chrome-trace profiler + histograms
# ---------------------------------------------------------------------------


def test_spans_land_in_chrome_trace_dump(tmp_path):
    fname = str(tmp_path / "trace.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    with telemetry.span("unit.test_span", "fit"):
        time.sleep(0.001)
    profiler.profiler_set_state("stop")
    profiler.dump_profile()
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    spans = [e for e in events if e["name"] == "unit.test_span"]
    assert spans, "telemetry span missing from the chrome trace"
    e = spans[0]
    assert e["ph"] == "X" and e["cat"] == "fit" and e["dur"] >= 1000  # >=1ms
    # ...and the same span observed its duration as a histogram
    assert telemetry.histogram("unit.test_span").count == 1


def test_span_is_only_an_annotation_when_everything_off():
    """With telemetry, the MXNet profiler and jax.profiler all off a span
    is its (idle) profiler annotation: no clock read, no histogram, no
    chrome-trace event."""
    telemetry.disable()
    assert not profiler.is_running()
    s = telemetry.span("off.span", "test", batch=3)
    with s:
        pass
    assert s._ann is not None and s._t0 is None
    telemetry.enable()
    assert telemetry.histogram("off.span").count == 0
    # the per-operator spans of the imperative path get no annotation
    assert profiler.record_span("off.op") is profiler._OFF


def test_concurrent_span_writers_and_profiler_toggle(tmp_path):
    """The satellite fix: spans appending while another thread flips
    profiler state / dumps must neither crash nor corrupt the buffer."""
    fname = str(tmp_path / "toggle.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    stop = threading.Event()

    def spam():
        while not stop.is_set():
            with telemetry.span("spam.span"):
                pass

    workers = [threading.Thread(target=spam) for _ in range(4)]
    for w in workers:
        w.start()
    for _ in range(20):
        profiler.profiler_set_state("run")
        time.sleep(0.001)
        profiler.profiler_set_state("stop")
        profiler.dump_profile()
    stop.set()
    for w in workers:
        w.join()
    with open(fname) as f:
        json.load(f)  # parseable = the buffer was never torn mid-dump


# ---------------------------------------------------------------------------
# events + file sink + flusher
# ---------------------------------------------------------------------------


def test_events_are_json_lines_in_sink(tmp_path):
    sink = str(tmp_path / "telemetry.jsonl")
    telemetry.start_flusher(path=sink, interval_s=3600)
    telemetry.event("epoch_start", epoch=0)
    telemetry.counter("sink.counter").inc()
    telemetry.flush()
    telemetry.stop_flusher()  # writes one final snapshot
    with open(sink) as f:
        recs = [json.loads(line) for line in f]
    kinds = [r["type"] for r in recs]
    assert "event" in kinds and "snapshot" in kinds
    ev = next(r for r in recs if r["type"] == "event")
    assert ev["event"] == "epoch_start" and ev["epoch"] == 0
    snap = next(r for r in recs if r["type"] == "snapshot")
    assert snap["counters"]["sink.counter"] == 1


def test_periodic_flusher_appends_snapshots(tmp_path):
    sink = str(tmp_path / "periodic.jsonl")
    telemetry.counter("flush.counter").inc()
    telemetry.start_flusher(path=sink, interval_s=0.05)
    deadline = time.time() + 5
    while time.time() < deadline:
        if os.path.exists(sink) and sum(
                1 for _ in open(sink)) >= 2:
            break
        time.sleep(0.02)
    telemetry.stop_flusher(final_flush=False)
    with open(sink) as f:
        recs = [json.loads(line) for line in f]
    snaps = [r for r in recs if r["type"] == "snapshot"]
    assert len(snaps) >= 2, "flusher never ticked"
    assert all(s["counters"]["flush.counter"] == 1 for s in snaps)


def test_env_autostart_enables_and_flushes(tmp_path):
    """MXNET_TELEMETRY_FILE at import => enabled registry + at-exit flush."""
    sink = str(tmp_path / "auto.jsonl")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_TELEMETRY_FILE": sink,
                "MXNET_TELEMETRY_INTERVAL_S": "3600",
                "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", "")})
    code = ("import mxnet_tpu as mx\n"
            "assert mx.telemetry.enabled()\n"
            "mx.telemetry.counter('auto.counter').inc(5)\n"
            "mx.telemetry.event('marker', step=1)\n")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=180)
    with open(sink) as f:
        recs = [json.loads(line) for line in f]
    assert any(r["type"] == "event" and r["event"] == "marker" for r in recs)
    final = [r for r in recs if r["type"] == "snapshot"][-1]
    assert final["counters"]["auto.counter"] == 5


# ---------------------------------------------------------------------------
# fit loop: step-time / data-wait / throughput metrics
# ---------------------------------------------------------------------------


def _toy_fit(batch_end_callback=None, num_epoch=2, batch_size=16):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    X = rng.rand(64, 10).astype(np.float32)
    y = rng.randint(0, 8, 64).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch_size)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, batch_end_callback=batch_end_callback,
            optimizer_params={"learning_rate": 0.01, "rescale_grad": 1.0})
    return mod


def test_module_fit_populates_step_metrics():
    _toy_fit()
    d = telemetry.dump()
    n_batches = 2 * (64 // 16)
    assert d["counters"]["fit.batches"] == n_batches
    assert d["counters"]["fit.samples"] == 2 * 64
    assert d["counters"]["fit.epochs"] == 2
    for name in ("fit.step_time_seconds", "fit.compute_seconds",
                 "fit.data_wait_seconds"):
        assert d["histograms"][name]["count"] >= n_batches, name
        assert d["histograms"][name]["sum"] > 0, name
    assert d["gauges"]["fit.imgs_per_sec"] > 0
    # data iterators recorded fetch latency
    assert d["histograms"]["io.batch_fetch_seconds{iter=NDArrayIter}"][
        "count"] >= n_batches
    # epoch markers arrived as structured events, in order
    marks = [(e["event"], e["epoch"]) for e in telemetry.events()
             if e["event"] in ("epoch_start", "epoch_end")]
    assert marks == [("epoch_start", 0), ("epoch_end", 0),
                     ("epoch_start", 1), ("epoch_end", 1)]
    end = telemetry.events("epoch_end")[-1]
    assert end["nbatch"] == 64 // 16 and "accuracy" in end["metrics"]


def test_speedometer_reads_registry_and_publishes_gauge(caplog):
    import logging

    with caplog.at_level(logging.INFO):
        _toy_fit(batch_end_callback=mx.callback.Speedometer(
            batch_size=16, frequent=2))
    assert telemetry.gauge("speedometer.samples_per_sec").value > 0
    logged = [r.message for r in caplog.records if "Speed:" in r.message]
    assert logged, "speedometer never logged"
    # the printed number and the registry agree (single source of truth)
    printed = float(re.search(r"Speed: ([0-9.]+)", logged[-1]).group(1))
    assert printed == pytest.approx(
        telemetry.gauge("speedometer.samples_per_sec").value, rel=1e-4)


def test_speedometer_auto_reset_honored():
    from mxnet_tpu.callback import Speedometer
    from mxnet_tpu.model import BatchEndParam

    def run(auto_reset):
        metric = mx.metric.Accuracy()
        metric.update([mx.nd.array(np.zeros(2))],
                      [mx.nd.array(np.zeros((2, 2)))])
        sp = Speedometer(batch_size=2, frequent=1, auto_reset=auto_reset)
        sp(BatchEndParam(epoch=0, nbatch=0, eval_metric=metric, locals=None))
        sp(BatchEndParam(epoch=0, nbatch=1, eval_metric=metric, locals=None))
        return metric.num_inst

    assert run(auto_reset=True) == 0      # window reset the metric
    assert run(auto_reset=False) == 2     # accumulation preserved


def test_disabled_fit_records_no_step_metrics():
    telemetry.disable()
    _toy_fit(num_epoch=1)
    d = telemetry.dump()
    assert "fit.step_time_seconds" not in d["histograms"]
    assert "fit.batches" not in d["counters"]
    assert d["events"] == []


# ---------------------------------------------------------------------------
# engine + fault + kvstore counters
# ---------------------------------------------------------------------------


def test_engine_push_metrics_and_error_counter():
    from mxnet_tpu.engine import NaiveEngine

    eng = NaiveEngine()
    eng.push(lambda: None)
    assert telemetry.counter("engine.pushes").value == 1
    assert telemetry.histogram("engine.push_latency_seconds").count == 1

    def boom():
        raise RuntimeError("pushed fn failure")

    eng.push(boom)
    with pytest.raises(RuntimeError):
        eng.wait_all()
    assert telemetry.counter("engine.push_errors").value == 1


def test_error_counters_count_even_when_disabled():
    telemetry.disable()
    from mxnet_tpu.engine import NaiveEngine

    eng = NaiveEngine()

    def boom():
        raise RuntimeError("x")

    eng.push(boom)
    with pytest.raises(RuntimeError):
        eng.wait_all()
    assert telemetry.counter("engine.push_errors").value == 1


def test_fault_injection_counter():
    with fault.inject("some_point:raise=1,times=2"):
        for _ in range(3):
            try:
                fault.hit("some_point")
            except fault.InjectedFault:
                pass
    assert telemetry.counter("fault.injections", point="some_point").value == 2


def test_local_kvstore_latency_histograms():
    kv = mx.kv.create("local")
    kv.init(3, mx.nd.ones((4,)))
    kv.push(3, mx.nd.ones((4,)))
    out = mx.nd.zeros((4,))
    kv.pull(3, out=out)
    assert telemetry.histogram("kvstore.push_latency_seconds", key=3).count == 1
    assert telemetry.histogram("kvstore.pull_latency_seconds", key=3).count == 1


class _FakeLib:
    """Stands in for the native transport in retry-loop tests: every server
    probe reports alive, so _with_retry classifies failures as transient."""

    def mxt_ps_probe(self, host, port, timeout_ms):
        return 0

    def mxt_ps_client_probe(self, client, cmd, timeout_ms):
        return 0


def _retry_harness():
    from mxnet_tpu.kvstore import KVStoreDist

    kv = object.__new__(KVStoreDist)  # no cluster: exercise only the retry loop
    kv._lib = _FakeLib()
    kv._server_addrs = [("127.0.0.1", 12345)]
    kv._num_servers = 1
    kv._clients = [object()]
    # group routing (server HA): one group, itself primary — the identity
    # map _sid_for degenerates to with no replicas
    kv._smap = [0]
    kv._ngroups = 1
    return kv


def test_kv_retry_counters_increment_under_fault_inject(monkeypatch):
    monkeypatch.setenv("MXNET_KV_RETRIES", "3")
    monkeypatch.setenv("MXNET_KV_TIMEOUT_MS", "100")
    kv = _retry_harness()

    def attempt():
        rule = fault.hit("kv_push")
        if rule is not None and rule.get("drop") not in (None, "0"):
            raise MXNetError("injected push drop")

    with fault.inject("kv_push:drop=1,times=2"):
        kv._with_retry("push", 0, attempt)  # 2 drops, 3rd attempt succeeds
    assert telemetry.counter("kvstore.retries", op="push").value == 2
    assert telemetry.counter("kvstore.rpc_failures", op="push").value == 2
    assert telemetry.counter("kvstore.backoff_ms", op="push").value > 0
    assert telemetry.counter("fault.injections", point="kv_push").value == 2


def test_kv_retry_exhaustion_counts_every_retry(monkeypatch):
    monkeypatch.setenv("MXNET_KV_RETRIES", "2")
    monkeypatch.setenv("MXNET_KV_TIMEOUT_MS", "100")
    kv = _retry_harness()

    def attempt():
        raise MXNetError("always fails")

    with pytest.raises(MXNetError, match="after 2 retries"):
        kv._with_retry("pull", 0, attempt)
    assert telemetry.counter("kvstore.retries", op="pull").value == 2
    assert telemetry.counter("kvstore.rpc_failures", op="pull").value == 3


# ---------------------------------------------------------------------------
# kvstore_server counters + request_server_stats dict (native cluster)
# ---------------------------------------------------------------------------


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


WORKER_SERVER_STATS = r"""
import numpy as np
import mxnet_tpu as mx

kv = mx.kv.create("dist_sync")
kv.init(5, mx.nd.zeros((4,)))
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5, rescale_grad=1.0))
for _ in range(3):
    kv.push(5, mx.nd.ones((4,)))
    out = mx.nd.zeros((4,))
    kv.pull(5, out=out)
stats = kv.request_server_stats()
assert len(stats) == 1, stats
(addr, s), = stats.items()
assert s is not None, "server published no stats"
assert s["has_optimizer"] is True, s
assert s["updates_applied"] >= 3, s
assert s["update_failures"] == 0, s
# user traffic still works after the reserved-key stats round-trip
kv.push(5, mx.nd.ones((4,)))
kv.pull(5, out=out)
print("STATS_DICT_OK", sorted(s.items()))
kv._stop_servers()
print("WORKER_OK")
"""


@needs_native
def test_request_server_stats_returns_parsed_dict():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DMLC_ROLE", None)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
           "-n", "1", "-s", "1", "--port", str(_free_port()),
           sys.executable, "-c", WORKER_SERVER_STATS]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=180)
    except subprocess.TimeoutExpired:
        import signal

        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        out, err = proc.communicate()
        raise AssertionError("cluster hung: %s %s" % (out, err))
    assert proc.returncode == 0, (out, err)
    assert "STATS_DICT_OK" in out, (out, err)
    assert "WORKER_OK" in out, (out, err)


# ---------------------------------------------------------------------------
# metric catalog: HELP lines + doc-drift killer (docs/observability.md)
# ---------------------------------------------------------------------------


def test_prometheus_help_lines_emitted():
    telemetry.counter("fit.batches").inc()
    telemetry.histogram("fit.step_time_seconds").observe(0.1)
    text = telemetry.prometheus_text()
    lines = text.splitlines()
    for pname in ("mxnet_fit_batches", "mxnet_fit_step_time_seconds"):
        help_idx = [i for i, l in enumerate(lines)
                    if l.startswith("# HELP %s " % pname)]
        type_idx = [i for i, l in enumerate(lines)
                    if l.startswith("# TYPE %s " % pname)]
        assert help_idx and type_idx, text
        assert help_idx[0] == type_idx[0] - 1  # HELP directly above TYPE


def _registered_metric_names():
    """Every metric name registered with a string literal anywhere in
    mxnet_tpu/ (counter/gauge/histogram/span/pipeline_stage first args).
    AST-based so multi-line calls and aliased imports are all caught."""
    import ast

    pkg = os.path.join(ROOT, "mxnet_tpu")
    names = {}
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                attr = (func.attr if isinstance(func, ast.Attribute)
                        else func.id if isinstance(func, ast.Name) else None)
                if attr not in ("counter", "gauge", "histogram", "span",
                                "pipeline_stage"):
                    continue
                if not node.args or not isinstance(node.args[0], ast.Constant) \
                        or not isinstance(node.args[0].value, str):
                    continue
                name = node.args[0].value
                if attr == "pipeline_stage":
                    name = "pipeline.stage_seconds"
                if "." not in name:
                    continue  # not a metric name (e.g. a span category)
                names.setdefault(name, os.path.relpath(path, ROOT))
    assert len(names) > 25, "scanner broke: found only %s" % sorted(names)
    return names


def test_every_registered_metric_is_documented():
    """Kills doc drift permanently: every metric name registered anywhere
    in mxnet_tpu/ must have a row in docs/observability.md AND an entry in
    the telemetry.METRIC_HELP catalog (which feeds # HELP exposition)."""
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        docs = f.read()
    missing_docs, missing_help = [], []
    for name, where in sorted(_registered_metric_names().items()):
        if "`%s`" % name not in docs and "`%s" % name not in docs:
            missing_docs.append("%s (registered in %s)" % (name, where))
        if name not in telemetry.METRIC_HELP:
            missing_help.append("%s (registered in %s)" % (name, where))
    assert not missing_docs, \
        "metrics missing a docs/observability.md row: %s" % missing_docs
    assert not missing_help, \
        "metrics missing a telemetry.METRIC_HELP entry: %s" % missing_help


# ---------------------------------------------------------------------------
# chrome-trace schema regression (tools/trace_merge.validate_trace)
# ---------------------------------------------------------------------------


def test_profiler_trace_passes_schema_validation(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_merge

    out = str(tmp_path / "trace.json")
    profiler.profiler_set_config(mode="all", filename=out)
    profiler.profiler_set_state("run")
    try:
        # nested + sequential spans across the runtime's emitters: the
        # nesting and per-tid monotonicity rules must hold in the dump
        with telemetry.span("outer.phase", "test", epoch=0):
            with telemetry.span("inner.phase", "test"):
                time.sleep(0.002)
            time.sleep(0.001)
        with telemetry.span("fit.step", "fit", epoch=0, nbatch=1):
            time.sleep(0.001)
    finally:
        profiler.profiler_set_state("stop")
    profiler.dump_profile()
    with open(out) as f:
        trace = json.load(f)
    assert trace_merge.validate_trace(trace) == []
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(evs) == 3
    # required fields on every span
    for ev in evs:
        for field in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert field in ev, ev
    # span args survive the dump (trace_merge matches steps by them)
    step = [e for e in evs if e["name"] == "fit.step"][0]
    assert step["args"] == {"epoch": 0, "nbatch": 1}
    # ts monotonic per tid in FILE ORDER (dump_profile sorts: spans are
    # appended at completion, inner-before-outer)
    per_tid = {}
    for ev in evs:
        per_tid.setdefault(ev["tid"], []).append(ev["ts"])
    for tid, series in per_tid.items():
        assert series == sorted(series), (tid, series)


def test_profiler_dump_carries_rank_metadata(tmp_path):
    telemetry.set_rank(3)
    out = str(tmp_path / "trace.json")
    profiler.profiler_set_config(mode="all", filename=out)
    profiler.profiler_set_state("run")
    with telemetry.span("x.y", "test"):
        pass
    profiler.profiler_set_state("stop")
    profiler.dump_profile()
    with open(out) as f:
        trace = json.load(f)
    meta = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert meta and meta[0]["args"]["rank"] == 3, trace["traceEvents"][:3]


# ---------------------------------------------------------------------------
# CI satellites: end-to-end flusher JSON + trace_merge smoke
# ---------------------------------------------------------------------------

FLUSHER_E2E = r"""
import numpy as np
import mxnet_tpu as mx

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
net = mx.sym.SoftmaxOutput(net, name="softmax")
rng = np.random.RandomState(0)
X = rng.rand(64, 10).astype(np.float32)
y = rng.randint(0, 8, 64).astype(np.float32)
it = mx.io.NDArrayIter(X, y, batch_size=16)
mod = mx.mod.Module(net, context=mx.cpu())
mod.fit(it, num_epoch=2)
print("FIT_OK")
"""


def test_telemetry_file_end_to_end_fit(tmp_path):
    """The background flusher, driven only by MXNET_TELEMETRY_FILE, must
    produce parseable JSON lines from a real fit: periodic + final
    snapshots with the fit metrics, and structured events interleaved."""
    sink = str(tmp_path / "telemetry.{rank}.jsonl")
    env = dict(os.environ)
    env.pop("DMLC_ROLE", None)
    env.update({"JAX_PLATFORMS": "cpu", "MXNET_TELEMETRY_FILE": sink,
                "MXNET_TELEMETRY_INTERVAL_S": "0.2", "DMLC_WORKER_ID": "4",
                "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", "")})
    r = subprocess.run([sys.executable, "-c", FLUSHER_E2E], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    resolved = str(tmp_path / "telemetry.4.jsonl")  # {rank} expanded
    with open(resolved) as f:
        recs = [json.loads(line) for line in f]  # every line parses
    snaps = [x for x in recs if x["type"] == "snapshot"]
    events = [x for x in recs if x["type"] == "event"]
    assert snaps, "flusher produced no snapshots"
    assert snaps[-1]["counters"]["fit.epochs"] == 2
    assert snaps[-1]["rank"] == 4
    assert any(e["event"] == "epoch_end" for e in events)
    assert all(e["rank"] == 4 for e in events)


def test_trace_merge_smoke_two_workers(tmp_path):
    """CI smoke (docs/observability.md §cluster): merge two synthetic
    worker traces -> one valid chrome trace with two pid lanes."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_merge

    for rank, skew in ((0, 0.0), (1, 1.25)):
        evs = [{"name": "process_name", "ph": "M", "pid": 100 + rank,
                "tid": 0, "args": {"name": "rank %d" % rank, "rank": rank}},
               {"name": "kv.barrier", "ph": "X", "cat": "kvstore",
                "ts": (50.0 + skew) * 1e6, "dur": 1e5,
                "pid": 100 + rank, "tid": 1, "args": {"seq": 1}},
               {"name": "fit.step", "ph": "X", "cat": "fit",
                "ts": (51.0 + skew) * 1e6, "dur": 5e5,
                "pid": 100 + rank, "tid": 1,
                "args": {"epoch": 0, "nbatch": 0}}]
        with open(tmp_path / ("w%d.json" % rank), "w") as f:
            json.dump({"traceEvents": evs}, f)
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_merge.py"),
         "-o", str(out), "--validate",
         str(tmp_path / "w0.json"), str(tmp_path / "w1.json")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr)
    merged = json.loads(out.read_text())
    assert trace_merge.validate_trace(merged) == []
    assert trace_merge.lane_pids(merged) == [0, 1]
    # the skew was recovered from the barrier sync point
    offs = merged["otherData"]["clock_offsets"]
    assert abs(offs["w1.json"]["offset_s"] + 1.25) < 1e-6, offs
