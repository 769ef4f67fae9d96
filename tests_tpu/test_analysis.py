"""Static-analysis + engine-sanitizer suite (docs/static_analysis.md).

Per fwlint checker: one synthetic positive and one negative case; plus
inline-suppression semantics, the baseline ratchet (seeded new violation
fails, paid-down debt reports stale), the CLI entry point, and the engine
dependency sanitizer (warn-mode counters, strict-mode classified raises,
use-after-free, and the disabled-by-default zero-instrumentation contract).

Host-side only: runs on a CPU-only machine (tests_tpu/conftest.py exempts
this file from the hardware gate). `ci/run_tests.sh lint` is the CI tier.
"""
import os
import sys
import textwrap
import threading

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import engine as engine_mod, telemetry  # noqa: E402
from mxnet_tpu.analysis import baseline as baseline_mod  # noqa: E402
from mxnet_tpu.analysis import fwlint, sanitizer  # noqa: E402
from mxnet_tpu.base import MXNetError, env_bool, env_str  # noqa: E402

pytestmark = pytest.mark.analysis

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src, path="mxnet_tpu/fake.py", select=None):
    return fwlint.lint_source(textwrap.dedent(src), path=path, select=select)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# checkers: positive + negative per rule
# ---------------------------------------------------------------------------

def test_env_raw_read_positive():
    src = """
    import os
    a = os.environ.get("MXNET_FOO", "1")
    b = os.getenv("MXNET_BAR")
    c = os.environ["MXNET_BAZ"]
    """
    found = lint(src, select=["env-raw-read"])
    assert len(found) == 3
    assert rules_of(found) == ["env-raw-read"]
    assert {f.line for f in found} == {3, 4, 5}


def test_env_raw_read_negative():
    src = """
    import os
    from .base import env_int
    a = env_int("MXNET_FOO", 1)            # helper: fine
    b = os.environ.get("DMLC_NUM_WORKER")  # not an MXNET_* knob
    os.environ["MXNET_SET"] = "1"          # write, not read
    key = "MXNET_DYN"
    c = os.environ.get(key)                # non-constant key: not flagged
    """
    assert lint(src, select=["env-raw-read"]) == []


def test_env_raw_read_exempt_in_base():
    src = 'import os\nv = os.environ.get("MXNET_X")\n'
    assert fwlint.lint_source(src, path="mxnet_tpu/base.py",
                              select=["env-raw-read"]) == []
    assert len(fwlint.lint_source(src, path="mxnet_tpu/other.py",
                                  select=["env-raw-read"])) == 1


def test_bare_except_positive_negative():
    src = """
    try:
        x = 1
    except:
        x = 2
    """
    found = lint(src, select=["bare-except"])
    assert rules_of(found) == ["bare-except"]
    # a bare except that re-raises is the cleanup idiom: not flagged
    src_ok = """
    try:
        x = 1
    except:
        cleanup()
        raise
    """
    assert lint(src_ok, select=["bare-except"]) == []


def test_swallowed_exception_positive_negative():
    src = """
    try:
        x = 1
    except Exception:
        pass
    """
    assert rules_of(lint(src, select=["swallowed-exception"])) == [
        "swallowed-exception"]
    # a handler that logs (or otherwise does work) is not a swallow
    src_ok = """
    try:
        x = 1
    except Exception:
        log.warning("boom")
    except ValueError:
        pass
    """
    # narrow except with pass is also fine — only BROAD handlers count
    assert lint(src_ok, select=["swallowed-exception"]) == []


def test_thread_hygiene_positive():
    src = """
    import threading
    t = threading.Thread(target=f)
    t.start()
    """
    found = lint(src, select=["thread-hygiene"])
    # unnamed AND neither daemon nor joined: two findings
    assert len(found) == 2


def test_thread_hygiene_negative():
    src = """
    import threading
    a = threading.Thread(target=f, name="worker", daemon=True)
    b = threading.Thread(target=f, name="joined-later")
    b.start()
    b.join()
    """
    assert lint(src, select=["thread-hygiene"]) == []


def test_thread_hygiene_self_attr_join():
    src = """
    import threading

    class A:
        def start(self):
            self._t = threading.Thread(target=self.run, name="a")
            self._t.start()

        def close(self):
            self._t.join()
    """
    assert lint(src, select=["thread-hygiene"]) == []


def test_lock_discipline_positive_negative():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._state = {}  # guarded-by: _lock

        def good(self):
            with self._lock:
                self._state["k"] = 1

        def bad(self):
            self._state["k"] = 2
    """
    found = lint(src, select=["lock-discipline"])
    assert len(found) == 1
    assert found[0].context.endswith("C.bad")
    # un-annotated attributes are never checked
    src_plain = src.replace("  # guarded-by: _lock", "")
    assert lint(src_plain, select=["lock-discipline"]) == []


def hot(src, select=("device-escape",)):
    """Lint under a hot-path file name (module/ scope)."""
    return fwlint.lint_source(textwrap.dedent(src),
                              path="mxnet_tpu/module/fake.py",
                              select=list(select))


def test_device_escape_explicit_forms_and_scoping():
    """The legacy vocabulary still fires in hot-path scope (the migrated
    baseline stays meaningful) and stays silent outside it."""
    src = """
    def step(arr, np):
        h = arr.asnumpy()
        s = arr.asscalar()
        n = np.asarray(arr)
    """
    assert len(hot(src)) == 3
    cold = fwlint.lint_source(textwrap.dedent(src),
                              path="mxnet_tpu/metric.py",
                              select=["device-escape"])
    assert cold == []


def test_device_escape_implicit_sync_forms():
    """Acceptance pin: implicit host syncs the PR 5 name-grep was blind
    to — float()/truthiness-in-if/np-ufunc/f-string/.item() on a TRACKED
    device value — are detected (5 forms >= the required 3)."""
    src = """
    from mxnet_tpu import ndarray as nd
    import numpy as np

    def step(batch):
        arr = nd.zeros((4, 4))
        a = float(arr)                  # implicit: dunder-float sync
        if arr > 0:                     # implicit: comparison truthiness
            pass
        m = np.mean(arr)                # implicit: host ufunc pulls
        msg = f"loss={arr}"             # implicit: formatting repr sync
        v = arr.item()                  # implicit: scalar materialize
        return a, m, msg, v
    """
    found = hot(src)
    assert len(found) == 5
    assert all(f.rule == "device-escape" for f in found)
    # every finding carries the dataflow chain naming the device source
    assert all(any("nd.zeros" in step for step in f.chain)
               for f in found)


def test_device_escape_implicit_needs_tracked_value():
    """float()/if on plain Python scalars must NOT fire — that is the
    precision the dataflow pass buys over a grep."""
    src = """
    def step(lr, nbatch):
        x = float(lr)
        if nbatch > 0:
            pass
        return x
    """
    assert hot(src) == []


def test_device_escape_host_proven_asarray_exempt():
    """np.asarray over a PROVABLY-host value no longer fires (the legacy
    grep flagged it): reassigning through .asnumpy() kills tracking."""
    src = """
    import numpy as np
    from mxnet_tpu import ndarray as nd

    def step():
        x = nd.ones((2,))
        x = x.asnumpy()      # explicit sync: flagged once, tracking killed
        y = np.asarray(x)    # x is now provably host: NOT flagged
        z = float(x)         # host float: NOT flagged
        return y, z
    """
    found = hot(src)
    assert len(found) == 1
    assert ".asnumpy()" in found[0].message


# ---------------------------------------------------------------------------
# dataflow propagation (the device-escape/trace-impure/recompile substrate)
# ---------------------------------------------------------------------------

def test_dataflow_tuple_unpack_propagates():
    src = """
    from mxnet_tpu import ndarray as nd

    def step():
        a, b = nd.ones((2,)), 3.0
        fa = float(a)      # a came from the device element: flagged
        fb = float(b)      # b is a host scalar: clean
        return fa, fb
    """
    found = hot(src)
    assert len(found) == 1
    assert found[0].line == 6


def test_dataflow_call_summary_same_file():
    """A same-file callee returning a device value taints its callers
    (the call-return summary half of the pass)."""
    src = """
    from mxnet_tpu import ndarray as nd

    def make():
        return nd.zeros((2, 2))

    def step():
        x = make()
        return float(x)
    """
    found = hot(src)
    assert len(found) == 1
    assert "same-file summary" in " ".join(found[0].chain)


def test_dataflow_reassignment_to_host_kills_tracking():
    src = """
    from mxnet_tpu import ndarray as nd

    def step():
        x = nd.ones((2,))
        x = [1, 2, 3]
        return float(x)    # x was re-bound to a host list: clean
    """
    assert hot(src) == []


def test_dataflow_annotated_param_and_executor_output_seeds():
    src = """
    def step(x: "NDArray", group):
        a = float(x)                 # annotated param: tracked
        outs = group.get_outputs()
        b = float(outs[0])           # executor output: tracked
        return a, b
    """
    found = hot(src)
    assert {f.line for f in found} == {3, 5}


def test_dataflow_attribute_and_meta_split():
    """x.data stays device; x.shape/x.dtype are trace-time metadata."""
    src = """
    from mxnet_tpu import ndarray as nd

    def step():
        x = nd.ones((2,))
        a = float(x.data)    # device payload attribute: flagged
        n = float(x.shape[0])  # metadata: clean
        return a, n
    """
    found = hot(src)
    assert len(found) == 1
    assert found[0].line == 6


# ---------------------------------------------------------------------------
# trace-impure
# ---------------------------------------------------------------------------

def test_trace_impure_side_effects_in_jitted_fn():
    src = """
    import time
    from mxnet_tpu import compileobs, telemetry

    _CACHE = []

    def step(x):
        telemetry.counter("steps").inc()   # side effect -> baked constant
        t = time.time()                    # trace-time clock read
        print(x)                           # stdout at trace time only
        _CACHE.append(x)                   # closure/global mutation
        return x * t

    fn = compileobs.jit(step, "prog")
    """
    found = lint(src, select=["trace-impure"])
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 4
    assert "telemetry.counter" in msgs and "time.time" in msgs
    assert "print" in msgs and "_CACHE" in msgs


def test_trace_impure_traced_value_control_flow():
    src = """
    from mxnet_tpu import compileobs

    def step(x):
        if x.sum() > 0:        # traced value: branch baked at trace time
            return x
        return -x

    fn = compileobs.jit(step, "prog")
    """
    found = lint(src, select=["trace-impure"])
    assert len(found) == 1
    assert "data-dependent" in found[0].message
    assert any("traced" in c for c in found[0].chain)


def test_trace_impure_negative_pure_and_structure_checks():
    """Pure math, local-list building (the flash-attention k_all idiom),
    `is None` structure branches, and functions NOT reaching jit are all
    clean."""
    src = """
    from mxnet_tpu import compileobs, telemetry

    def step(x, rng):
        if rng is None:          # structure check: re-traced per structure
            acc = []
            for i in range(4):
                acc.append(x * i)   # LOCAL list: trace-legal
            return sum(acc[1:], acc[0])
        return x

    def untraced(x):
        telemetry.counter("n").inc()   # not under trace: fine
        return x

    fn = compileobs.jit(step, "prog")
    """
    assert lint(src, select=["trace-impure"]) == []


def test_trace_impure_factory_closure_and_cross_file_reach():
    """The serving-engine shape: compileobs.jit(_mk()) jits a closure the
    factory returns, and the closure's callee in ANOTHER file is also
    under trace."""
    main_src = textwrap.dedent("""
    import pkg.helper as H
    from mxnet_tpu import compileobs

    def _mk():
        def _step(x):
            return H.inner(x)
        return _step

    fn = compileobs.jit(_mk(), "prog")
    """)
    helper_src = textwrap.dedent("""
    def inner(x):
        print(x)
        return x * 2
    """)
    from mxnet_tpu.analysis import checkers as checkers_mod

    ctxs = [fwlint.FileContext("pkg/main.py", main_src),
            fwlint.FileContext("pkg/helper.py", helper_src)]
    found = checkers_mod.check_trace_impure(ctxs)
    assert len(found) == 1
    assert found[0].path == "pkg/helper.py"
    assert "print" in found[0].message


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------

def test_recompile_hazard_per_step_scalar_and_shape_ctor():
    src = """
    import numpy as np
    from mxnet_tpu import compileobs

    class M:
        def __init__(self, fn):
            self._fwd = compileobs.jit(fn, "m.fwd")

        def run(self, data, nbatch):
            self._fwd(data, nbatch)            # per-step scalar by name
            for i, b in enumerate(data):
                self._fwd(np.zeros(len(b)))    # shape from unbucketed len
                self._fwd(data, i)             # enumerate counter
    """
    found = lint(src, select=["recompile-hazard"])
    assert len(found) == 3
    assert all("fresh XLA program" in f.message for f in found)
    # --explain material: chains name the per-step origin
    assert any("per-step scalar by name" in " ".join(f.chain)
               for f in found)
    assert any("len(" in " ".join(f.chain) for f in found)


def test_recompile_hazard_bucketed_and_traced_scalars_clean():
    """The two sanctioned launderings: routing through a *bucket* helper,
    and wrapping the scalar into a traced np scalar (shape-stable)."""
    src = """
    import numpy as np
    from mxnet_tpu import compileobs

    BUCKETS = (32, 64, 128)

    def bucket_for(n, buckets):
        return 64

    class M:
        def __init__(self, fn):
            self._fwd = compileobs.jit(fn, "m.fwd")

        def run(self, data):
            L = len(data)
            self._fwd(np.int32(L))                  # traced 0-d: stable
            S = bucket_for(len(data), BUCKETS)
            self._fwd(np.zeros(S))                  # bucketed: stable
            toks = np.zeros((1, S), np.int32)
            self._fwd(toks)
    """
    assert lint(src, select=["recompile-hazard"]) == []


def test_recompile_hazard_ctor_through_local_and_kwarg():
    """A shape-ctor result bound to a name first — the common real-world
    spelling — and a keyword argument both carry the hazard."""
    src = """
    import numpy as np
    from mxnet_tpu import compileobs

    class M:
        def __init__(self, fn):
            self._fwd = compileobs.jit(fn, "m.fwd")

        def run(self, data):
            n = len(data)
            pad = np.zeros(n)
            self._fwd(pad)             # ctor routed through a local
            self._fwd(mask=np.ones(n))  # keyword argument
    """
    found = lint(src, select=["recompile-hazard"])
    assert len(found) == 2
    assert all("shape derives from a per-step scalar"
               in " ".join(f.chain) for f in found)


def test_lock_order_string_and_path_join_not_blocking():
    """os.path.join / str.join under a shared lock are not Thread.join:
    no deadlock-class finding (review fix); a real thread join still
    flags."""
    src = """
    import os
    import threading

    class B:
        def __init__(self):
            self._lock = threading.Lock()
            self._flusher = threading.Thread(target=f, name="x",
                                             daemon=True)

        def harmless(self):
            with self._lock:
                p = os.path.join("a", "b")
                s = ", ".join(["x", "y"])
            return p, s

        def wedges(self):
            with self._lock:
                self._flusher.join()

        def other(self):
            with self._lock:
                pass
    """
    found = lint(src, select=["lock-order"])
    assert len(found) == 1
    assert "Thread.join()" in found[0].message
    assert found[0].line == 19  # the self._flusher.join() line


def test_recompile_hazard_slice_bound_and_wrapper_dict():
    src = """
    import numpy as np
    from mxnet_tpu import compileobs

    class M:
        def __init__(self, mk):
            self._jits = {b: compileobs.jit(mk(), "m.fwd")
                          for b in (1, 2, 4)}

        def run(self, x, data):
            n = len(data)
            self._jits[1](x[:n])     # slice bound varies per step
    """
    found = lint(src, select=["recompile-hazard"])
    assert len(found) == 1
    assert "slice bound" in " ".join(found[0].chain)


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------

def test_lock_order_lexical_cycle():
    src = """
    import threading

    class A:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._b:
                with self._a:
                    pass
    """
    found = lint(src, select=["lock-order"])
    assert len(found) == 1
    assert "cycle" in found[0].message and "deadlock" in found[0].message


def test_lock_order_transitive_cycle_through_call():
    """The fixpoint half: outer() holds _x and CALLS inner() which takes
    _y; reverse() nests them the other way — a cycle no lexical scan
    sees."""
    src = """
    import threading

    class D:
        def __init__(self):
            self._x = threading.Lock()
            self._y = threading.Lock()

        def outer(self):
            with self._x:
                self.inner()

        def inner(self):
            with self._y:
                pass

        def reverse(self):
            with self._y:
                with self._x:
                    pass
    """
    found = lint(src, select=["lock-order"])
    assert len(found) == 1
    assert "cycle" in found[0].message


def test_lock_order_consistent_order_clean():
    src = """
    import threading

    class A:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._a:
                with self._b:
                    pass
    """
    assert lint(src, select=["lock-order"]) == []


def test_lock_order_blocking_under_shared_lock():
    src = """
    import queue
    import threading

    class B:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()

        def worker(self):
            with self._lock:
                item = self._q.get()
            return item

        def other(self):
            with self._lock:
                return 1
    """
    found = lint(src, select=["lock-order"])
    assert len(found) == 1
    assert "queue.get()" in found[0].message


def test_lock_order_condition_wait_on_held_lock_exempt():
    """Condition.wait RELEASES the lock it wraps — the serving engine's
    run_loop idiom must stay clean; an Event.wait under a shared lock
    must not."""
    src_ok = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.RLock()
            self._work = threading.Condition(self._lock)

        def run_loop(self):
            with self._work:
                self._work.wait(timeout=0.05)

        def submit(self):
            with self._work:
                pass
    """
    assert lint(src_ok, select=["lock-order"]) == []
    src_bad = src_ok.replace("self._work.wait(timeout=0.05)",
                             "self._ev.wait(timeout=0.05)")
    found = lint(src_bad, select=["lock-order"])
    assert len(found) == 1
    assert ".wait()" in found[0].message


def test_lock_order_transitive_blocking_through_helper():
    """The motivating shape: the queue pop lives in a HELPER the
    lock-holder calls — still flagged (blocking propagates through the
    call fixpoint, not just lexical scope)."""
    src = """
    import queue
    import threading

    class B:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()

        def driver(self):
            with self._lock:
                return self._drain()

        def _drain(self):
            return self._q.get()

        def other(self):
            with self._lock:
                return 1
    """
    found = lint(src, select=["lock-order"])
    assert len(found) == 1
    assert "queue.get()" in found[0].message
    assert "_drain" in found[0].message   # names the helper it reached


def test_lock_order_condition_wait_helper_exempt():
    """Condition.wait split into a helper stays exempt when the caller
    holds the condition's own lock (the wait releases it)."""
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.RLock()
            self._work = threading.Condition(self._lock)

        def run_loop(self):
            with self._work:
                self._idle()

        def _idle(self):
            self._work.wait(timeout=0.05)

        def submit(self):
            with self._work:
                pass
    """
    assert lint(src, select=["lock-order"]) == []


def test_lock_discipline_module_lock_cannot_satisfy_class_owned():
    """Symmetric to the module-half fix: a class-OWNED lock needs the
    instance lock — the same-named module `with _lock:` is a different
    lock."""
    src = """
    import threading

    _lock = threading.Lock()

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._state = {}  # guarded-by: _lock

        def wrong(self):
            with _lock:
                self._state["x"] = 1

        def right(self):
            with self._lock:
                self._state["y"] = 2
    """
    found = lint(src, select=["lock-discipline"])
    assert len(found) == 1
    assert found[0].context.endswith("wrong")


def test_device_escape_and_recompile_hazard_at_module_scope():
    """Module-level statements (tools/ scripts) are a dataflow scope
    too: implicit escapes and jit-wrapper hazards fire outside defs, and
    AnnAssign-bound wrappers are recognized."""
    esc = hot("""
    from mxnet_tpu import ndarray as nd

    arr = nd.zeros((2,))
    x = float(arr)
    """)
    assert len(esc) == 1
    hz = lint("""
    import numpy as np
    from mxnet_tpu import compileobs

    fn: object = compileobs.jit(step, "prog")
    n = len(data)
    out = fn(np.zeros(n))
    """, select=["recompile-hazard"])
    assert len(hz) == 1


def test_lock_order_blocking_under_private_lock_clean():
    """A blocking call under a lock only ONE function ever takes cannot
    wedge another thread's handler path: not flagged."""
    src = """
    import queue
    import threading

    class B:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()

        def worker(self):
            with self._lock:
                return self._q.get()
    """
    assert lint(src, select=["lock-order"]) == []


# ---------------------------------------------------------------------------
# lock-discipline: the PR 5 alias/module-level gaps
# ---------------------------------------------------------------------------

def test_lock_discipline_local_alias_resolves():
    src = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._state = {}  # guarded-by: _lock

        def good(self):
            lk = self._lock
            with lk:
                self._state["k"] = 1
    """
    assert lint(src, select=["lock-discipline"]) == []


def test_lock_discipline_alias_of_any_lock_name():
    """Alias resolution is not name-shape-gated: `mu = self._mutex`
    resolves even though 'mutex' matches no lock-ish pattern."""
    src = """
    import threading

    class C:
        def __init__(self):
            self._mutex = threading.Lock()
            self._state = {}  # guarded-by: _mutex

        def good(self):
            mu = self._mutex
            with mu:
                self._state["k"] = 1
    """
    assert lint(src, select=["lock-discipline"]) == []


def test_lock_discipline_local_shadow_of_module_name():
    """A function-local binding of a guarded module-level name is a
    DIFFERENT variable: not checked (Python scoping, not bare-name
    matching); `global` re-links it."""
    src = """
    import threading

    _lock = threading.Lock()
    _state = {}  # guarded-by: _lock

    def local_shadow():
        _state = {}
        _state["x"] = 1      # local variable: clean

    def global_writer():
        global _state
        _state = {}          # the guarded global, unlocked: flagged
    """
    found = lint(src, select=["lock-discipline"])
    assert len(found) == 1
    assert found[0].context.endswith("global_writer")


def test_device_escape_boolop_test_single_report():
    """`if arr and flag:` is ONE sync, not two findings (the BoolOp join
    is covered operand-by-operand)."""
    src = """
    from mxnet_tpu import ndarray as nd

    def step(flag):
        arr = nd.ones((2,))
        if arr and flag:
            return 1
    """
    found = hot(src)
    assert len(found) == 1
    assert "and/or" in found[0].message


def test_lock_discipline_module_level_lock():
    src = """
    import threading

    _lock = threading.Lock()
    _state = {}  # guarded-by: _lock

    def good():
        with _lock:
            _state["x"] = 1

    def bad():
        return _state.get("x")
    """
    found = lint(src, select=["lock-discipline"])
    assert len(found) == 1
    assert found[0].context.endswith("bad")


def test_lock_discipline_class_lock_cannot_satisfy_module_annotation():
    """A class's same-named `with self._lock:` is a DIFFERENT lock than
    the module-level `_lock` a module annotation names (the telemetry.py
    shape: module _lock + instrument classes each with self._lock)."""
    src = """
    import threading

    _lock = threading.Lock()
    _state = {}  # guarded-by: _lock

    class C:
        def __init__(self):
            self._lock = threading.Lock()

        def wrong_lock(self):
            with self._lock:
                _state["x"] = 1
    """
    found = lint(src, select=["lock-discipline"])
    assert len(found) == 1
    assert found[0].context.endswith("wrong_lock")


def test_device_escape_call_as_truthiness_test():
    """`if arr.sum():` forces the device boolean exactly like
    `if arr > 0:` — a Call in test position is checked too."""
    src = """
    from mxnet_tpu import ndarray as nd

    def step():
        arr = nd.ones((2,))
        if arr.sum():
            return 1
    """
    found = hot(src)
    assert len(found) == 1
    assert "truthiness" in found[0].message


def test_recompile_hazard_multidim_slice_bound():
    """`x[:, :n]` (the normal rank-2 batch spelling) carries the per-step
    slice-bound hazard just like `x[:n]`."""
    src = """
    from mxnet_tpu import compileobs

    class M:
        def __init__(self, fn):
            self._fwd = compileobs.jit(fn, "m.fwd")

        def run(self, x, data):
            n = len(data)
            self._fwd(x[:, :n])
    """
    found = lint(src, select=["recompile-hazard"])
    assert len(found) == 1
    assert "slice bound" in " ".join(found[0].chain)


def test_device_escape_outputs_seed_and_any_truthiness():
    """Executor `.outputs` elements are device-seeded whatever we know
    about the executor, `.any()` truthiness flags — and len() of the
    outputs LIST (graph arity, a static property) stays clean."""
    src = """
    def step(exec_, group):
        out = exec_.outputs[0]
        a = float(out)              # element of .outputs: tracked
        if out.any():               # truthiness reduction: tracked
            pass
        n = len(exec_.outputs)      # list arity: clean
        outs = group.get_outputs()
        m = len(outs)               # same arity via the accessor: clean
        return a, n, m
    """
    found = hot(src)
    assert {f.line for f in found} == {4, 5}


def test_lock_discipline_async_with():
    src = """
    import threading

    _lock = threading.Lock()
    _state = {}  # guarded-by: _lock

    async def good():
        async with _lock:
            _state["x"] = 1
    """
    assert lint(src, select=["lock-discipline"]) == []


def test_import_alias_map_package_asname():
    """`import pkg.sub as alias` resolves through sub/__init__.py too."""
    src = "import pkg.sub as S\n"
    ctx = fwlint.FileContext("main.py", src)
    amap = fwlint.import_alias_map(ctx, {"pkg/sub/__init__.py", "main.py"})
    assert amap["S"] == "pkg/sub/__init__.py"


def test_import_alias_map_dotted_import_binds_root():
    """`import a.b` (no asname) binds the ROOT name `a`; resolving
    `a.<attr>` against a/b.py would read the wrong symbol table."""
    src = textwrap.dedent("""
    import pkg.helper
    import pkg.helper as H
    """)
    ctx = fwlint.FileContext("main.py", src)
    paths = {"pkg/__init__.py", "pkg/helper.py", "main.py"}
    amap = fwlint.import_alias_map(ctx, paths)
    assert amap["pkg"] == "pkg/__init__.py"
    assert amap["H"] == "pkg/helper.py"


def test_untracked_jit_positive():
    fs = lint(
        """
        import jax

        def build(fn):
            return jax.jit(fn, donate_argnums=(0,))
        """, select=["untracked-jit"])
    assert rules_of(fs) == ["untracked-jit"]
    fs = lint(
        """
        import jax

        def export(fn, specs):
            return jax.export.export(jax.jit(fn))(*specs)
        """, select=["untracked-jit"])
    assert len(fs) == 2  # the export AND the inner jit


def test_untracked_jit_bare_import_form():
    fs = lint(
        """
        from jax import jit

        def build(fn):
            return jit(fn)
        """, select=["untracked-jit"])
    assert rules_of(fs) == ["untracked-jit"]


def test_untracked_jit_decorator_and_partial_forms():
    # `@jax.jit` puts jax.jit in the tree as a bare Attribute (decorator),
    # `partial(jax.jit, ...)` as a Call ARGUMENT — neither is a Call whose
    # func is jax.jit, and both compile untracked programs
    fs = lint(
        """
        import jax

        @jax.jit
        def step(x):
            return x
        """, select=["untracked-jit"])
    assert rules_of(fs) == ["untracked-jit"]
    fs = lint(
        """
        import functools
        import jax

        def build(fn):
            return functools.partial(jax.jit, donate_argnums=(0,))(fn)
        """, select=["untracked-jit"])
    assert rules_of(fs) == ["untracked-jit"]


def test_untracked_jit_negative_registry_forms():
    fs = lint(
        """
        from mxnet_tpu import compileobs

        def build(fn, other):
            a = compileobs.jit(fn, "fused.step")
            b = compileobs.raw_jit(fn, "export.x")
            c = other.jit(fn)  # not jax's
            return a, b, c
        """, select=["untracked-jit"])
    assert fs == []


def test_untracked_jit_exempt_in_compileobs():
    fs = lint(
        """
        import jax

        def wrap(fn):
            return jax.jit(fn)
        """, path="mxnet_tpu/compileobs.py", select=["untracked-jit"])
    assert fs == []


def test_mutable_default_arg():
    src = """
    def f(a, b=[], c={}, d=dict()):
        return a

    def ok(a, b=None, c=(), d="x"):
        return a
    """
    found = lint(src, select=["mutable-default-arg"])
    assert len(found) == 3
    assert all(f.context.endswith("f") for f in found)


# ---------------------------------------------------------------------------
# suppressions + fingerprints + baseline ratchet
# ---------------------------------------------------------------------------

def test_inline_suppression_same_line_and_line_above():
    src = """
    import os
    a = os.environ.get("MXNET_A")  # fwlint: disable=env-raw-read — reason
    # fwlint: disable=env-raw-read — reason
    b = os.environ.get("MXNET_B")
    c = os.environ.get("MXNET_C")  # fwlint: disable=thread-hygiene (wrong rule)
    """
    found = lint(src, select=["env-raw-read"])
    assert [f.line for f in found] == [6]  # only the wrong-rule one survives


def test_trailing_suppression_does_not_leak_to_next_line():
    # ratchet soundness: a pragma trailing line N must NOT exempt line N+1
    src = """
    import os
    a = os.environ.get("MXNET_A")  # fwlint: disable=env-raw-read — reason
    b = os.environ.get("MXNET_B")
    """
    found = lint(src, select=["env-raw-read"])
    assert [f.line for f in found] == [4]
    assert "MXNET_B" in found[0].message


def test_suppression_with_ascii_hyphen_reason():
    src = """
    import os
    a = os.environ.get("MXNET_A")  # fwlint: disable=env-raw-read - a reason
    b = os.environ.get("MXNET_B")  # fwlint: disable=env-raw-read,bare-except - x
    """
    assert lint(src, select=["env-raw-read"]) == []


def test_cli_update_baseline_refuses_partial_runs(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fwlint_cli3", os.path.join(ROOT, "tools", "fwlint.py"))
    cli_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli_mod)
    # a typo'd path must be a hard error (rc=2), never a green 0-file run
    assert cli_mod.main(["--root", ROOT, "mxnet_tpux"]) == 2
    bl = tmp_path / "bl.json"
    # --select and explicit paths both narrow the scope: refuse (rc=2) and
    # leave the baseline file untouched
    assert cli_mod.main(["--baseline", str(bl), "--update-baseline",
                         "--select", "env-raw-read", "--root", ROOT]) == 2
    assert cli_mod.main(["--baseline", str(bl), "--update-baseline",
                         "mxnet_tpu/engine.py", "--root", ROOT]) == 2
    assert not bl.exists()


def test_fingerprint_stable_under_line_drift():
    src = 'import os\nv = os.environ.get("MXNET_X")\n'
    drifted = "import os\n# a comment pushing things down\n\n" \
              'v = os.environ.get("MXNET_X")\n'
    fp1 = fwlint.lint_source(src, path="m.py")[0].fingerprint
    fp2 = fwlint.lint_source(drifted, path="m.py")[0].fingerprint
    assert fp1 == fp2


def test_baseline_ratchet(tmp_path):
    repo = tmp_path / "repo"
    (repo / "pkg").mkdir(parents=True)
    mod = repo / "pkg" / "m.py"
    mod.write_text('import os\nv = os.environ.get("MXNET_X")\n')
    bl = repo / "baseline.json"

    # freeze current debt
    findings = fwlint.lint_paths(["pkg"], str(repo))
    assert len(findings) == 1
    baseline_mod.save(str(bl), findings)

    # unchanged tree: ok
    new, known, stale = fwlint.run_lint(["pkg"], root=str(repo),
                                        baseline_path=str(bl))
    assert (len(new), len(known), stale) == (0, 1, [])

    # seeded NEW violation: the ratchet fails exactly on it
    mod.write_text('import os\nv = os.environ.get("MXNET_X")\n'
                   'w = os.environ.get("MXNET_Y")\n')
    new, known, _ = fwlint.run_lint(["pkg"], root=str(repo),
                                    baseline_path=str(bl))
    assert len(known) == 1 and len(new) == 1
    assert "MXNET_Y" in new[0].message

    # debt paid down: finding gone, baseline entry reported stale
    mod.write_text("v = 1\n")
    new, known, stale = fwlint.run_lint(["pkg"], root=str(repo),
                                        baseline_path=str(bl))
    assert (new, known) == ([], []) and len(stale) == 1


def test_cli_on_repo_with_committed_baseline(tmp_path):
    """Acceptance: exit 0 on the repo + committed baseline; non-zero when a
    new violation is seeded on top of the SAME baseline."""
    cli = os.path.join(ROOT, "tools", "fwlint.py")
    import importlib.util

    spec = importlib.util.spec_from_file_location("fwlint_cli", cli)
    cli_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli_mod)

    assert cli_mod.main(["--baseline", "ci/fwlint_baseline.json",
                         "--root", ROOT]) == 0

    seeded = tmp_path / "seeded.py"
    seeded.write_text('import os\nv = os.environ.get("MXNET_SEEDED_NEW")\n')
    rc = cli_mod.main(["--baseline", os.path.join(ROOT, "ci",
                                                  "fwlint_baseline.json"),
                       "--root", str(tmp_path), "seeded.py"])
    assert rc == 1


def test_cli_list_rules(capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fwlint_cli2", os.path.join(ROOT, "tools", "fwlint.py"))
    cli_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli_mod)
    assert cli_mod.main(["--list-rules"]) == 0
    out = capsys.readouterr().out.split()
    for rule in ("env-raw-read", "bare-except", "swallowed-exception",
                 "thread-hygiene", "lock-discipline", "device-escape",
                 "trace-impure", "recompile-hazard", "lock-order",
                 "mutable-default-arg", "untracked-jit"):
        assert rule in out
    # the superseded name-grep rule is GONE, not aliased
    assert "host-sync-in-hot-path" not in out


def test_cli_dump_lock_graph(capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fwlint_cli4", os.path.join(ROOT, "tools", "fwlint.py"))
    cli_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli_mod)
    # acceptance: the repo's lock graph is cycle-free -> exit 0
    assert cli_mod.main(["--dump-lock-graph", "--root", ROOT]) == 0
    dot = capsys.readouterr().out
    assert dot.startswith("digraph lock_order")
    # real content, not a vacuous pass: the known hierarchy edges exist
    assert "ServingEngine._lock" in dot
    assert '"mxnet_tpu.serving.engine.ServingEngine._lock" -> ' \
           '"mxnet_tpu.serving.kv_cache.KVBlockPool._lock"' in dot


def test_cli_explain_prints_chain(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fwlint_cli5", os.path.join(ROOT, "tools", "fwlint.py"))
    cli_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli_mod)
    mod = tmp_path / "m.py"
    mod.write_text(textwrap.dedent("""
    from mxnet_tpu import ndarray as nd

    def step():
        x = nd.zeros((2,))
        y = x
        return float(y)
    """))
    # find the fingerprint via the json report, then explain it
    out_json = tmp_path / "report.json"
    cli_mod.main(["--root", str(tmp_path), "--json-out", str(out_json),
                  "m.py"])
    capsys.readouterr()
    import json as _json

    rec = _json.load(out_json.open())
    hits = [f for f in rec["new"] if f["rule"] == "device-escape"]
    # tmp_path file is outside hot-path scope: re-run against a hot path
    mod2 = tmp_path / "mxnet_tpu" / "module"
    mod2.mkdir(parents=True)
    (mod2 / "fake.py").write_text(mod.read_text())
    cli_mod.main(["--root", str(tmp_path), "--json-out", str(out_json),
                  "mxnet_tpu/module/fake.py"])
    capsys.readouterr()
    rec = _json.load(out_json.open())
    hits = [f for f in rec["new"] if f["rule"] == "device-escape"]
    assert len(hits) == 1 and hits[0]["chain"]
    fp = hits[0]["fingerprint"]
    rc = cli_mod.main(["--root", str(tmp_path), "--explain", fp[:10],
                       "mxnet_tpu/module/fake.py"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "taint chain" in out and "nd.zeros" in out


def test_finding_chain_not_part_of_fingerprint():
    """Chain wording can improve without churning the baseline."""
    src = textwrap.dedent("""
    from mxnet_tpu import ndarray as nd

    def step():
        x = nd.zeros((2,))
        return float(x)
    """)
    f = fwlint.lint_source(src, path="mxnet_tpu/module/fake.py",
                           select=["device-escape"])[0]
    assert f.chain
    g = fwlint.Finding(f.rule, f.path, f.line, f.col, f.message,
                       context=f.context, text=f.text, chain=())
    import mxnet_tpu.analysis.fwlint as _fw

    _fw._finalize([g])
    assert g.fingerprint == f.fingerprint


def test_repo_is_clean_under_committed_baseline():
    new, known, stale = fwlint.run_lint(
        ["mxnet_tpu", "tools"], root=ROOT,
        baseline_path=os.path.join(ROOT, "ci", "fwlint_baseline.json"))
    assert new == [], "new fwlint violations: %s" % new
    assert stale == [], ("baseline entries no longer fire — run "
                         "`python tools/fwlint.py --baseline "
                         "ci/fwlint_baseline.json --update-baseline`")


@pytest.mark.parametrize("rule", ["device-escape", "trace-impure",
                                  "recompile-hazard", "lock-order",
                                  "unguarded-shared-write", "check-then-act",
                                  "unbalanced-acquire", "guard-mismatch"])
def test_new_rules_repo_clean_or_baselined(rule, _repo_lint):
    """Per-rule acceptance: each new rule family runs repo-wide and every
    finding it raises is frozen in the committed baseline (the ratchet
    seeds shrink-only debt; lock-order and trace-impure are at 0)."""
    new = [f for f in _repo_lint[0] if f.rule == rule]
    assert new == [], "unbaselined %s findings: %s" % (rule, new)


@pytest.fixture(scope="module")
def _repo_lint():
    return fwlint.run_lint(
        ["mxnet_tpu", "tools"], root=ROOT,
        baseline_path=os.path.join(ROOT, "ci", "fwlint_baseline.json"))


def test_device_escape_debt_is_zero_and_cannot_regrow():
    """Round 13 burned the step-path host-sync debt to nothing: the
    committed baseline carries ZERO device-escape entries (it reached 0
    via the parallel_module init/set_params device-side loads and the
    fused_path states upload), every surviving entry — there are none
    today, but the assertion is shape-proof — names a live rule, and a
    fresh device-escape in a hot path is reported as NEW under the
    committed baseline, so the debt cannot silently regrow."""
    import json as _json

    doc = _json.load(open(os.path.join(ROOT, "ci",
                                       "fwlint_baseline.json")))
    rules = [rec["rule"] for rec in doc["findings"].values()]
    assert all(r in fwlint.RULES for r in rules)
    assert "host-sync-in-hot-path" not in rules
    assert rules.count("device-escape") == 0, (
        "device-escape step-path debt regrew into the baseline: %s"
        % [r for r in doc["findings"].values()
           if r["rule"] == "device-escape"])
    # regrow guard: a seeded hot-path device escape must surface as NEW
    # (the ratchet fails CI on it) — an empty baseline can never absorb it
    src = textwrap.dedent("""
    from mxnet_tpu import ndarray as nd

    def step():
        x = nd.zeros((2,))
        return float(x)
    """)
    findings = fwlint.lint_source(src, path="mxnet_tpu/module/seeded.py",
                                  select=["device-escape"])
    assert len(findings) == 1
    baseline = baseline_mod.load(os.path.join(ROOT, "ci",
                                              "fwlint_baseline.json"))
    new, known, _ = baseline_mod.diff(findings, baseline)
    assert len(new) == 1 and known == []


# ---------------------------------------------------------------------------
# base.env_* helpers (new in this PR: env_bool / env_str)
# ---------------------------------------------------------------------------

def test_env_bool_strict_parse(monkeypatch):
    monkeypatch.setenv("MXNET_T_BOOL", "yes")
    assert env_bool("MXNET_T_BOOL") is True
    monkeypatch.setenv("MXNET_T_BOOL", "off")
    assert env_bool("MXNET_T_BOOL", True) is False
    monkeypatch.setenv("MXNET_T_BOOL", "garbage")
    assert env_bool("MXNET_T_BOOL", True) is True  # warn + default
    monkeypatch.delenv("MXNET_T_BOOL")
    assert env_bool("MXNET_T_BOOL") is False


def test_env_str_choices(monkeypatch):
    monkeypatch.setenv("MXNET_T_STR", "WARN")
    assert env_str("MXNET_T_STR", None, choices=("warn", "strict")) == "warn"
    monkeypatch.setenv("MXNET_T_STR", "bogus")
    assert env_str("MXNET_T_STR", "off", choices=("warn",)) == "off"
    monkeypatch.setenv("MXNET_T_STR", "  plain  ")
    assert env_str("MXNET_T_STR") == "plain"
    monkeypatch.delenv("MXNET_T_STR")
    assert env_str("MXNET_T_STR", "d") == "d"


# ---------------------------------------------------------------------------
# engine dependency sanitizer
# ---------------------------------------------------------------------------

@pytest.fixture
def naive_engine():
    eng = engine_mod.NaiveEngine()
    yield eng
    sanitizer.configure(None)


def _counter(kind):
    return telemetry.counter(sanitizer.COUNTER_PREFIX + kind).value


def test_sanitizer_warn_counts_undeclared_mutation(naive_engine):
    eng = naive_engine
    a, b = mx.nd.ones((2,)), mx.nd.ones((2,))
    va, vb = eng.new_variable(), eng.new_variable()
    sanitizer.attach(a, va)
    sanitizer.attach(b, vb)
    sanitizer.configure("warn")
    before = _counter("undeclared_mutation")
    eng.push(lambda: b._set_data(b.data * 2), const_vars=[va])
    eng.wait_all()  # warn mode: no raise
    assert _counter("undeclared_mutation") == before + 1
    assert b.asnumpy()[0] == 2.0  # the fn itself still ran to completion


def test_sanitizer_strict_raises_at_wait(naive_engine):
    eng = naive_engine
    a, b = mx.nd.ones((2,)), mx.nd.ones((2,))
    va, vb = eng.new_variable(), eng.new_variable()
    sanitizer.attach(a, va)
    sanitizer.attach(b, vb)
    sanitizer.configure("strict")
    eng.push(lambda: b._set_data(b.data * 2), const_vars=[va])
    with pytest.raises(sanitizer.EngineSanitizerError) as ei:
        eng.wait_all()
    assert ei.value.kind == "undeclared_mutation"
    assert isinstance(ei.value, MXNetError)
    # the error slot is read-and-clear: the engine stays usable
    eng.push(lambda: None, mutable_vars=[vb])
    eng.wait_all()


def test_sanitizer_const_write(naive_engine):
    eng = naive_engine
    a = mx.nd.ones((2,))
    va = eng.new_variable()
    sanitizer.attach(a, va)
    sanitizer.configure("strict")
    eng.push(lambda: a._set_data(a.data + 1), const_vars=[va])
    with pytest.raises(sanitizer.EngineSanitizerError) as ei:
        eng.wait_all()
    assert ei.value.kind == "const_write"


def test_sanitizer_declared_access_clean(naive_engine):
    eng = naive_engine
    a, b = mx.nd.ones((2,)), mx.nd.ones((2,))
    va, vb = eng.new_variable(), eng.new_variable()
    sanitizer.attach(a, va)
    sanitizer.attach(b, vb)
    sanitizer.configure("strict")
    eng.push(lambda: b._set_data(a.data * 3), const_vars=[va],
             mutable_vars=[vb])
    eng.wait_all()
    assert b.asnumpy()[0] == 3.0


def test_sanitizer_use_after_free_at_push(naive_engine):
    eng = naive_engine
    va = eng.new_variable()
    sanitizer.configure("strict")
    eng.delete_variable(va)
    with pytest.raises(sanitizer.EngineSanitizerError) as ei:
        eng.push(lambda: None, const_vars=[va])
    assert ei.value.kind == "use_after_free"


def test_sanitizer_use_after_free_inside_fn(naive_engine):
    eng = naive_engine
    a = mx.nd.ones((2,))
    va = eng.new_variable()
    sanitizer.attach(a, va)
    sanitizer.configure("strict")
    # the fn closes over an array whose var is deleted mid-flight; declare
    # nothing so only the in-fn access trips
    eng.delete_variable(va)
    eng.push(lambda: a.data)
    with pytest.raises(sanitizer.EngineSanitizerError) as ei:
        eng.wait_all()
    assert ei.value.kind == "use_after_free"


def test_sanitizer_view_routes_to_base_var(naive_engine):
    eng = naive_engine
    a = mx.nd.ones((2, 2))
    va = eng.new_variable()
    sanitizer.attach(a, va)
    view = mx.nd.NDArray(None, ctx=a.context, base=a, index=0)
    assert sanitizer.var_of(view) is va


def test_sanitizer_undeclared_read_never_raises(naive_engine):
    eng = naive_engine
    a = mx.nd.ones((2,))
    va = eng.new_variable()
    sanitizer.attach(a, va)
    sanitizer.configure("strict")
    before = _counter("undeclared_read")
    eng.push(lambda: a.data)  # read, undeclared: counter only
    eng.wait_all()
    assert _counter("undeclared_read") == before + 1


def test_sanitizer_disabled_leaves_default_path_untouched():
    from mxnet_tpu.ndarray import NDArray

    sanitizer.configure(None)
    # acceptance: zero instrumentation when off — the accessors are the
    # pristine class-level definitions, not wrappers
    assert NDArray.data.fget.__qualname__ == "NDArray.data"
    assert NDArray._set_data.__qualname__ == "NDArray._set_data"
    sanitizer.configure("warn")
    assert NDArray.data.fget.__qualname__ != "NDArray.data"
    sanitizer.configure(None)
    assert NDArray.data.fget.__qualname__ == "NDArray.data"


def test_sanitizer_threaded_engine_strict():
    """The seeded undeclared-mutation race of the acceptance criteria, on
    the real threaded engine when the native lib is available."""
    try:
        eng = engine_mod.ThreadedEngine()
    except RuntimeError:
        pytest.skip("native runtime unavailable")
    try:
        a, b = mx.nd.ones((2,)), mx.nd.ones((2,))
        va, vb = eng.new_variable(), eng.new_variable()
        sanitizer.attach(a, va)
        sanitizer.attach(b, vb)
        sanitizer.configure("strict")
        # declares only a read of va but races a write into vb behind the
        # scheduler's back
        eng.push(lambda: b._set_data(b.data + 1), const_vars=[va])
        with pytest.raises(sanitizer.EngineSanitizerError):
            eng.wait_all()
    finally:
        sanitizer.configure(None)


def test_sanitizer_env_configuration(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_SANITIZER", "warn")
    sanitizer._mode = sanitizer._UNSET  # force a re-read of the env
    try:
        assert sanitizer.mode() == "warn"
        assert sanitizer.active()
    finally:
        sanitizer.configure(None)
    monkeypatch.setenv("MXNET_ENGINE_SANITIZER", "bogus")
    sanitizer._mode = sanitizer._UNSET
    try:
        assert sanitizer.mode() is None  # garbage degrades to off, no crash
    finally:
        sanitizer.configure(None)


# ---------------------------------------------------------------------------
# concurrency analyzer: thread roots, shared state, guards
# ---------------------------------------------------------------------------

def test_shared_write_two_roots_positive():
    """A field written from a spawned thread AND from main, with no lock
    anywhere: the canonical race the annotation-driven rules cannot see."""
    src = """
    import threading

    class Stats:
        def __init__(self):
            self.count = 0

        def _worker(self):
            self.count = self.count + 1

        def start(self):
            threading.Thread(target=self._worker, name="stats-worker").start()

        def reset(self):
            self.count = 0
    """
    found = lint(src, select=["unguarded-shared-write"])
    assert len(found) == 1
    f = found[0]
    assert f.line == 9  # the first unguarded write anchors the finding
    assert "thread(stats-worker)" in f.message and "main" in f.message
    assert "no lock held at any access" in f.message
    # the chain names BOTH racing roots and every bad write site
    assert any("thread(stats-worker)" in s for s in f.chain)
    assert any("root main" in s for s in f.chain)
    assert any("Stats.reset" in s for s in f.chain)


def test_publish_once_is_clean():
    """Writes confined to __init__ are publication, not a race — reads
    from any number of roots stay silent."""
    src = """
    import threading

    class Cfg:
        def __init__(self):
            self.limit = 8

        def _worker(self):
            return self.limit

        def start(self):
            threading.Thread(target=self._worker).start()

        def read(self):
            return self.limit
    """
    assert lint(src, select=["unguarded-shared-write"]) == []


def test_dominant_lock_outlier():
    """Three of four accesses hold the lock: it is the inferred guard, and
    the one bypassing write is the finding (message proposes guarded-by)."""
    src = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.val = 0

        def _worker(self):
            with self._lock:
                self.val = self.val + 1

        def start(self):
            threading.Thread(target=self._worker).start()

        def read(self):
            with self._lock:
                return self.val

        def smash(self):
            self.val = 0
    """
    found = lint(src, select=["unguarded-shared-write"])
    assert len(found) == 1
    f = found[0]
    assert f.context == "Box.smash"  # the outlier, not the guarded sites
    assert "guarded by mxnet_tpu.fake.Box._lock at 3 of 4 accesses" \
        in f.message
    assert "# guarded-by: _lock" in f.message
    assert any("guarded access under" in s for s in f.chain)


def test_fully_guarded_single_access_is_clean():
    """Regression: ONE live access, lock held — the dominant-lock vote
    used to null the lock below two holders and then flag the guarded
    write itself. Every-access-holds-the-lock must stay silent."""
    src = """
    import threading

    class Sched:
        def __init__(self):
            self._lock = threading.Lock()
            self.preempts = 0

        def _bump(self):
            with self._lock:
                self.preempts = 1

        def _loop(self):
            self._bump()

        def start(self):
            threading.Thread(target=self._loop).start()

        def drive(self):
            self._bump()
    """
    assert lint(src, select=["unguarded-shared-write"]) == []


def test_check_then_act_positive():
    src = """
    import threading

    class Gate:
        def __init__(self):
            self._lock = threading.Lock()
            self.open = False

        def _worker(self):
            with self._lock:
                self.open = True

        def start(self):
            threading.Thread(target=self._worker).start()

        def maybe_close(self):
            if self.open:
                with self._lock:
                    self.open = False
    """
    found = lint(src, select=["check-then-act"])
    assert len(found) == 1
    f = found[0]
    assert f.line == 17  # anchored at the unlocked read in the test
    assert "check-then-act on shared state mxnet_tpu.fake.Gate.open" \
        in f.message
    assert "the write at line 19" in f.message


def test_check_then_act_negative_lock_spans_test_and_set():
    src = """
    import threading

    class Gate:
        def __init__(self):
            self._lock = threading.Lock()
            self.open = False

        def _worker(self):
            with self._lock:
                self.open = True

        def start(self):
            threading.Thread(target=self._worker).start()

        def maybe_close(self):
            with self._lock:
                if self.open:
                    self.open = False
    """
    assert lint(src, select=["check-then-act"]) == []


def test_alias_resolved_guard_is_clean():
    """`lk = self._lock; with lk:` is the same guard — the alias resolves
    through lockgraph's local-binding pass, so no outlier is reported."""
    src = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.val = 0

        def _worker(self):
            lk = self._lock
            with lk:
                self.val = 1

        def start(self):
            threading.Thread(target=self._worker).start()

        def read(self):
            with self._lock:
                return self.val
    """
    assert lint(src, select=["unguarded-shared-write"]) == []


def test_handler_thread_root_and_per_connection_exemption():
    """A request-handler class is a thread root (one connection = one
    handler thread): a module global it writes races main, but its own
    self-state is per-connection and exempt wholesale."""
    src = """
    from http.server import BaseHTTPRequestHandler

    hits = 0

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            global hits
            hits = hits + 1
            self.cache = 1

    def report():
        return hits
    """
    found = lint(src, select=["unguarded-shared-write"])
    assert len(found) == 1
    f = found[0]
    assert "mxnet_tpu.fake.hits" in f.message
    assert "http-handler(Handler)" in f.message
    assert "Handler.cache" not in "".join(x.message for x in found)


def test_race_ok_annotation_needs_a_reason():
    base = """
    import threading

    class Stats:
        def __init__(self):
            self.count = 0{ann}

        def _worker(self):
            self.count = self.count + 1

        def start(self):
            threading.Thread(target=self._worker).start()

        def reset(self):
            self.count = 0
    """
    with_reason = base.format(
        ann="  # race-ok: a monotonically wrong debug tally")
    assert lint(with_reason, select=["unguarded-shared-write"]) == []
    bare = base.format(ann="  # race-ok:")
    assert len(lint(bare, select=["unguarded-shared-write"])) == 1
    # class-level form: the whole class's attrs are exempt
    confined = base.format(ann="").replace(
        "class Stats:",
        "# thread-confined: built fresh inside every test\n    class Stats:")
    assert lint(confined, select=["unguarded-shared-write"]) == []


def test_unbalanced_acquire_positive_and_handoff_negative():
    src = """
    import threading

    class A:
        def __init__(self):
            self._lock = threading.Lock()

        def bad(self):
            self._lock.acquire()
            return 1
    """
    found = lint(src, select=["unbalanced-acquire"])
    assert len(found) == 1
    assert "_lock.acquire() with no release() in A.bad" in found[0].message
    # balanced try/finally and the __enter__/__exit__-style cross-function
    # handoff are both fine
    src_ok = """
    import threading

    class A:
        def __init__(self):
            self._lock = threading.Lock()

        def hold(self):
            self._lock.acquire()

        def drop(self):
            self._lock.release()

        def balanced(self):
            self._lock.acquire()
            try:
                return 1
            finally:
                self._lock.release()
    """
    assert lint(src_ok, select=["unbalanced-acquire"]) == []


def test_manual_acquire_inside_a_with_body_outlives_the_with():
    """The engine step times its lock wait under a span
    (``with span(...): self._lock.acquire()``): the lock is held AFTER
    that ``with`` closes, so the guarded writes that follow are guarded —
    while a lock the ``with`` itself took is dropped at its end."""
    src = """
    import threading
    from contextlib import nullcontext

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.val = 0{init}

        def _worker(self):
            with nullcontext():
                self._lock.acquire()
            try:
                self.val = 1
            finally:
                self._lock.release()

        def start(self):
            threading.Thread(target=self._worker).start()

        def read(self):
            with self._lock:
                return self.val{more}
    """
    assert lint(src.format(init="", more=""),
                select=["unguarded-shared-write", "unbalanced-acquire"]) == []
    leaked = src.format(init="\n            self.n = 0", more="""

        def bump(self):
            with self._lock:
                pass
            self.n = self.n + 1

        def start2(self):
            threading.Thread(target=self.bump).start()
            self.n = 5""")
    found = lint(leaked, select=["unguarded-shared-write"])
    assert [("Box.n" in f.message) for f in found] == [True]


def test_guard_mismatch_positive_and_negative():
    src = """
    import threading

    class B:
        def __init__(self):
            self.lk_a = threading.Lock()
            self.lk_b = threading.Lock()
            self.val = 0  # guarded-by: lk_a

        def _worker(self):
            with self.lk_b:
                self.val = self.val + 1

        def start(self):
            threading.Thread(target=self._worker).start()

        def read(self):
            with self.lk_b:
                return self.val
    """
    found = lint(src, select=["guard-mismatch"])
    assert len(found) == 1
    f = found[0]
    assert f.line == 8  # the lying annotation, not the accesses
    assert "annotated `# guarded-by: lk_a`" in f.message
    assert "actually hold mxnet_tpu.fake.B.lk_b" in f.message
    fixed = src.replace("guarded-by: lk_a", "guarded-by: lk_b")
    assert lint(fixed, select=["guard-mismatch"]) == []


def test_select_is_per_rule_for_multi_rule_checkers():
    """The concurrency checker carries four rules: selecting one must not
    leak findings for the others (the shape that seeds both a race and a
    check-then-act fires exactly the selected family)."""
    src = """
    import threading

    class Gate:
        def __init__(self):
            self._lock = threading.Lock()
            self.open = False

        def _worker(self):
            with self._lock:
                self.open = True

        def start(self):
            threading.Thread(target=self._worker).start()

        def maybe_close(self):
            if self.open:
                with self._lock:
                    self.open = False
    """
    assert rules_of(lint(src, select=["check-then-act"])) \
        == ["check-then-act"]
    assert rules_of(lint(src, select=["unguarded-shared-write"])) \
        == ["unguarded-shared-write"]


def test_concurrency_debt_is_bounded_and_cannot_regrow():
    """The round-20 triage burned the concurrency debt down to the eight
    KVStoreDist client-side entries; three of the four rule families are
    at zero. The ratchet (plus this cap) keeps it shrink-only."""
    import json as _json

    doc = _json.load(open(os.path.join(ROOT, "ci",
                                       "fwlint_baseline.json")))
    rules = [rec["rule"] for rec in doc["findings"].values()]
    assert rules.count("unguarded-shared-write") <= 8
    for r in ("check-then-act", "unbalanced-acquire", "guard-mismatch"):
        assert rules.count(r) == 0, "new %s debt froze into the baseline" % r
    assert all(rec["path"] == "mxnet_tpu/kvstore.py"
               for rec in doc["findings"].values()
               if rec["rule"] == "unguarded-shared-write")


def test_cli_dump_thread_roots(capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fwlint_cli5", os.path.join(ROOT, "tools", "fwlint.py"))
    cli_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli_mod)
    assert cli_mod.main(["--dump-thread-roots", "--root", ROOT]) == 0
    out = capsys.readouterr().out
    # real discovery, not a vacuous table: the profiler's atexit hook, a
    # named repo thread, and the implicit main root all appear
    assert "atexit(_dump_at_exit)" in out
    assert "thread(mxnet-kv-membership-monitor)" in out
    assert "main  (spawned at <main>:0" in out


# ---------------------------------------------------------------------------
# runtime lock-order witness
# ---------------------------------------------------------------------------

@pytest.fixture
def witness_mode():
    from mxnet_tpu.analysis import witness

    witness.reset_observations()
    yield witness
    witness.configure(None)
    witness.seed_static(None)
    witness.reset_observations()


def test_witness_off_is_pristine(witness_mode):
    w = witness_mode
    w.configure(None)
    lk = threading.Lock()
    # acceptance: zero instrumentation when off — declare() hands back the
    # very same stdlib object, not a proxy
    assert w.declare("mxnet_tpu.fake.Off._lock", lk) is lk


def test_witness_env_configuration(monkeypatch, witness_mode):
    w = witness_mode
    monkeypatch.setenv("MXNET_LOCK_WITNESS", "strict")
    w._mode = w._UNSET  # force a re-read of the env
    assert w.mode() == "strict" and w.active()
    monkeypatch.setenv("MXNET_LOCK_WITNESS", "bogus")
    w._mode = w._UNSET
    assert w.mode() is None  # garbage degrades to off, no crash


def test_witness_warn_counters(witness_mode):
    w = witness_mode
    w.configure("warn")
    a = w.declare("mxnet_tpu.fake.WA", threading.Lock())
    b = w.declare("mxnet_tpu.fake.WB", threading.Lock())
    order_before = telemetry.counter(w.COUNTER_ORDER).value
    held_before = telemetry.histogram(w.HELD_HISTOGRAM,
                                      lock="mxnet_tpu.fake.WA").count
    with a:
        with b:
            pass
    assert ("mxnet_tpu.fake.WA", "mxnet_tpu.fake.WB") in w.observed_edges()
    assert telemetry.histogram(w.HELD_HISTOGRAM,
                               lock="mxnet_tpu.fake.WA").count \
        == held_before + 1
    # the reverse nesting is an order inversion: counted, logged, NO raise
    with b:
        with a:
            pass
    assert telemetry.counter(w.COUNTER_ORDER).value == order_before + 1
    # contention: a failed first probe is counted even when non-blocking
    c = w.declare("mxnet_tpu.fake.WC", threading.Lock())
    cont_before = telemetry.counter(w.CONTENTION_COUNTER,
                                    lock="mxnet_tpu.fake.WC").value
    assert c.acquire() is True
    assert c.acquire(blocking=False) is False
    assert telemetry.counter(w.CONTENTION_COUNTER,
                             lock="mxnet_tpu.fake.WC").value \
        == cont_before + 1
    c.release()


def test_witness_strict_raises_and_releases(witness_mode):
    w = witness_mode
    w.configure("strict")
    a = w.declare("mxnet_tpu.fake.SA", threading.Lock())
    b = w.declare("mxnet_tpu.fake.SB", threading.Lock())
    with a:
        with b:
            pass
    with pytest.raises(w.LockWitnessError) as ei:
        with b:
            with a:
                pass
    assert ei.value.kind == "order_inversion"
    assert isinstance(ei.value, MXNetError)
    # the failed acquisition holds nothing: the inner lock was handed back
    # when the violation raised out of acquire()
    assert a.acquire(blocking=False) is True
    a.release()
    assert b.acquire(blocking=False) is True
    b.release()


def test_witness_strict_unknown_edge(witness_mode):
    w = witness_mode
    w.configure("strict")
    a = w.declare("mxnet_tpu.fake.UA", threading.Lock())
    b = w.declare("mxnet_tpu.fake.UB", threading.Lock())
    c = w.declare("mxnet_tpu.fake.UC", threading.Lock())
    w.seed_static({("mxnet_tpu.fake.UA", "mxnet_tpu.fake.UB")})
    with a:      # the statically known edge passes silently
        with b:
            pass
    with pytest.raises(w.LockWitnessError) as ei:
        with a:  # A->C is an edge the static graph does not contain
            with c:
                pass
    assert ei.value.kind == "unknown_edge"


def test_witness_static_dynamic_agreement_three_locks(witness_mode):
    """Acceptance harness: seed the witness from lockgraph's OWN edge set
    for a 3-lock hierarchy, replay the same nesting at runtime in strict
    mode — zero violations; an off-graph nesting raises."""
    from mxnet_tpu.analysis import lockgraph

    w = witness_mode
    src = textwrap.dedent("""
    import threading

    class Eng:
        def __init__(self):
            self.la = threading.Lock()
            self.lb = threading.Lock()
            self.lc = threading.Lock()

        def step(self):
            with self.la:
                with self.lb:
                    with self.lc:
                        pass
    """)
    graph = lockgraph.build([fwlint.FileContext("mxnet_tpu/fake3.py", src)])
    edges = set(graph.edges)
    ids = {s for e in edges for s in e}
    assert edges == {("mxnet_tpu.fake3.Eng.la", "mxnet_tpu.fake3.Eng.lb"),
                     ("mxnet_tpu.fake3.Eng.la", "mxnet_tpu.fake3.Eng.lc"),
                     ("mxnet_tpu.fake3.Eng.lb", "mxnet_tpu.fake3.Eng.lc")}
    w.configure("strict")
    w.seed_static(edges)
    la, lb, lc = (w.declare(i, threading.Lock()) for i in sorted(ids))
    before = telemetry.counter(w.COUNTER_ORDER).value
    with la:
        with lb:
            with lc:
                pass
    with la:
        with lc:  # skipping the middle lock is still a static edge
            pass
    assert telemetry.counter(w.COUNTER_ORDER).value == before
    assert w.observed_edges() == edges
    ld = w.declare("mxnet_tpu.fake3.Eng.ld", threading.Lock())
    with pytest.raises(w.LockWitnessError) as ei:
        with la:
            with ld:
                pass
    assert ei.value.kind == "unknown_edge"
    assert telemetry.counter(w.COUNTER_ORDER).value == before + 1


def test_witness_condition_integration(witness_mode):
    """Condition(witnessed_lock) must work end-to-end: wait() releases the
    proxy for the notifier thread and the hold-time histogram observes
    each distinct hold."""
    w = witness_mode
    w.configure("warn")
    lk = w.declare("mxnet_tpu.fake.CV._lock", threading.RLock())
    cv = threading.Condition(lk)
    hits = []

    def poke():
        with cv:
            hits.append(1)
            cv.notify_all()

    t = threading.Thread(target=poke, name="witness-poke", daemon=True)
    held_before = telemetry.histogram(w.HELD_HISTOGRAM,
                                      lock="mxnet_tpu.fake.CV._lock").count
    with cv:
        t.start()
        cv.wait(timeout=5.0)
    t.join(timeout=5.0)
    assert hits == [1]
    # at least: the waiter's pre-wait hold and the notifier's hold
    assert telemetry.histogram(w.HELD_HISTOGRAM,
                               lock="mxnet_tpu.fake.CV._lock").count \
        >= held_before + 2


# ---------------------------------------------------------------------------
# regression tests for the races the analyzer found in this repo
# ---------------------------------------------------------------------------

class _ProbeLock:
    """Counts acquisitions; delegates the actual exclusion to an RLock."""

    def __init__(self):
        self.acquires = 0
        self._lk = threading.RLock()

    def acquire(self, blocking=True, timeout=-1):
        self.acquires += 1
        return self._lk.acquire(blocking, timeout)

    def release(self):
        self._lk.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False


def test_engine_abort_flags_read_under_lock():
    """serving.engine races fixed this round: handler threads poll
    `draining`/`aborted` against the driver's locked writes — the
    properties must take the engine lock."""
    from mxnet_tpu.serving import engine as serving_engine

    eng = object.__new__(serving_engine.ServingEngine)
    probe = _ProbeLock()
    eng._lock = probe
    eng._draining = True
    eng._aborted = "boom"
    assert eng.draining is True
    assert eng.aborted == "boom"
    assert probe.acquires == 2


def test_step_sync_meter_wait_accumulates_under_lock():
    """kvstore._StepSyncMeter race fixed this round: `wait_seconds +=` is
    a read-modify-write racing engine-thread add_busy() calls — it must
    hold the meter lock like every other accumulation."""
    from mxnet_tpu import kvstore as kv_mod

    m = kv_mod._StepSyncMeter()
    probe = _ProbeLock()
    m._lock = probe
    m.wait(lambda: None)
    assert probe.acquires == 1 and m.wait_seconds > 0.0
    m.add_busy(0.25)
    assert probe.acquires == 2
    assert 0.0 < m.overlap_seconds() <= 0.25
    assert probe.acquires == 3


def test_membership_resume_from_seeds_under_lock():
    """kvstore_server race fixed this round: registry failover re-runs
    _resume_from on a live object whose monitor thread is scanning the
    same maps — the whole seed must happen under the registry lock."""
    from mxnet_tpu import kvstore_server as kvs

    reg = object.__new__(kvs.MembershipRegistry)
    probe = _ProbeLock()
    reg._lock = probe
    reg._resume_from({"epoch": 3, "formed": True, "done": False,
                      "pos": None, "steps": {"0": 7},
                      "workers": {"0": 0.1}, "servers": {"1": 0.2},
                      "smap": [1, None], "srv_monitoring": True})
    assert probe.acquires == 1
    assert reg._epoch == 3 and reg._formed is True
    assert reg._smap == [1, None] and 1 in reg._srv_alive


def test_kv_pool_init_refreshes_gauges_under_lock():
    """serving.kv_cache race fixed this round: the pool may be built on a
    supervisor thread while handler threads poll a predecessor's gauges —
    the init-path gauge refresh honors the _locked suffix."""
    from mxnet_tpu.serving import kv_cache as kvc

    calls = []

    class Probe(kvc.KVBlockPool):
        def _refresh_gauges_locked(self):
            calls.append(self._lock.locked())
            return super()._refresh_gauges_locked()

    Probe(num_layers=1, num_blocks=2, block_size=2, num_heads=1,
          head_dim=2)
    assert calls and calls[0] is True
