#!/usr/bin/env bash
# CI entry point (reference: Jenkinsfile + tests/ci_build/ci_build.sh — the
# docker-matrix build/test driver). One stage per reference CI axis:
#   unit      python unit tests on the virtual 8-device CPU mesh (not slow)
#   native    C++ runtime build + native-path tests
#   compiler  graph-pass pipeline + persistent compile cache suite (fast in
#             `all`; cross-process warm-start e2e + deep parity when invoked
#             directly)
#   faults    fault-injection / robustness suite (fast, host-only)
#   telemetry runtime-telemetry + cluster-observability + compile-observability
#             suite: registry/exposition/fit metrics/trace identity/straggler/
#             trace_merge/compile accounting + recompile attribution + OOM
#             forensics (host-only; slow e2e acceptance cases run when invoked
#             directly)
#   pipeline  input-pipeline feed suite: uint8 wire + async device feed (fast, host-only)
#   perf      communication-overlap suite: bucket planner + 2-worker overlap
#             smoke + bucketed-vs-monolithic bit-identity (fast, host-only;
#             the slow elastic-rejoin A/B runs when invoked directly)
#   guard     training health-guard suite: sentinel/rollback/stall/resume (fast, host-only)
#   elastic   elastic-membership suite incl. the slow kill/rejoin e2e (host-only CPU mesh)
#   server_ha parameter-server HA suite: replicated groups / failover /
#             durable slots incl. the slow kill-a-primary e2e (host-only CPU mesh)
#   serving   paged-KV serving engine: kernel numerics/allocator/scheduler/
#             engine-vs-sequential equality (the files live in tests/, so
#             `unit` runs their fast cases; this stage runs them alone and
#             adds the slow >=32-stream HTTP e2e)
#   lint      fwlint invariant analyzer (ratchets on ci/fwlint_baseline.json) + analysis suite
#   deep      (opt-in, non-blocking) slow-marked deep-model compiles
#   predict   C predict shim build + compiled-client test
#   entry     driver contract: graft entry compile + multichip dryrun
set -euo pipefail
cd "$(dirname "$0")/.."

stage="${1:-all}"

run_unit() {
  # Process-level sharding (the reference CI sharded its matrix by suite,
  # Jenkinsfile:1-30; a single-stream run of tests/ passed 25 min in round
  # 3). Files are dealt size-descending round-robin across shards, each
  # shard is its own pytest process, and the stage fails if any shard
  # fails. MXTPU_TEST_SHARDS=1 restores the serial run.
  #
  # The PJRT-plugin suites (predict_native/train_native) have their own
  # stage AND talk to the real chip through subprocess C clients — a chip
  # belongs to one process, so inside the parallel shards they contend for
  # it and fail; keep them out of the unit stage unconditionally.
  # slow-marked tests (deep-model compiles) run in the non-blocking `deep`
  # stage; keeping them out of unit is what lets the per-test ceiling sit
  # at 300s (tier-1 verify filters the same marker)
  set -- "$@" -m "not slow" \
              --ignore=tests/test_predict_native.py \
              --ignore=tests/test_train_native.py
  local shards="${MXTPU_TEST_SHARDS:-6}"
  if [ "$shards" -le 1 ]; then
    local slog=/tmp/mxtpu_unit_serial.log
    local rc1=0
    python -m pytest tests/ -x -q --durations=25 "$@" 2>&1 | tee "$slog" \
      || rc1=1
    if [ "$rc1" = 0 ]; then
      # serial timings are ~3.5x smaller than the sharded baseline —
      # report beside it, never over it
      python tools/check_test_durations.py "$slog" \
        --ceiling "${MXTPU_TEST_CEILING:-180}" \
        --report /tmp/mxtpu_timings_serial.txt || rc1=1
    fi
    return $rc1
  fi
  # honor --ignore=... args from the `all` stage
  local ignores=()
  for a in "$@"; do
    case "$a" in --ignore=*) ignores+=("${a#--ignore=}") ;; esac
  done
  # deal the MEASURED-slowest files first, heaviest to lightest, so the
  # round-robin spreads them one per shard (tests/TIMINGS.txt per-file
  # totals from the last full run; file size remains the proxy for the
  # rest). Re-derive when the table shifts:
  #   python tools/check_test_durations.py <logs> --report -   (stdout)
  local slow_first="tests/test_models_deep2.py tests/test_kvstore_dist.py \
tests/test_parallel_lm.py tests/test_models.py tests/test_tutorials.py \
tests/test_module_fused.py tests/test_cpp_package.py tests/test_module.py \
tests/test_misc.py tests/test_parallel_modes.py tests/test_models_deep.py"
  for f in $slow_first; do
    [ -f "$f" ] || { echo "slow_first file missing: $f" >&2; return 1; }
  done
  mapfile -t files < <(
    printf '%s\n' $slow_first
    ls -S tests/test_*.py | grep -vxF "$(printf '%s\n' $slow_first)")
  local groups=()
  for i in $(seq 0 $((shards - 1))); do groups[i]=""; done
  local gi=0 skip f
  for f in "${files[@]}"; do
    skip=0
    for ig in "${ignores[@]:-}"; do [ "$f" = "$ig" ] && skip=1; done
    [ "$skip" = 1 ] && continue
    groups[gi]="${groups[gi]} $f"
    gi=$(((gi + 1) % shards))
  done
  local pids=() logs=() t0 rc=0
  t0=$(date +%s)
  for i in $(seq 0 $((shards - 1))); do
    [ -z "${groups[i]}" ] && continue
    logs[i]="/tmp/mxtpu_unit_shard_$i.log"
    # shellcheck disable=SC2086
    (set +e; python -m pytest ${groups[i]} -q -m "not slow" --durations=25 \
       > "${logs[i]}" 2>&1; echo $? > "${logs[i]}.rc") &
    pids[i]=$!
  done
  for i in "${!pids[@]}"; do
    wait "${pids[i]}" || true
    local shard_rc
    shard_rc=$(cat "${logs[i]}.rc" 2>/dev/null || echo 1)
    echo "--- shard $i (rc=$shard_rc): $(tail -1 "${logs[i]}")"
    if [ "$shard_rc" != 0 ]; then
      echo "=== shard $i FAILED; last 60 lines:"
      tail -60 "${logs[i]}"
      rc=1
    fi
  done
  echo "unit suite wall: $(($(date +%s) - t0))s across $shards shards"
  # per-test ceiling + merged timings report (the budget lever that works
  # on a 1-core host; tools/check_test_durations.py). Only THIS run's
  # shard logs — a /tmp glob would merge stale runs' timings.
  if [ "$rc" = 0 ]; then
    local this_logs=()
    for i in "${!logs[@]}"; do
      [ -n "${logs[i]}" ] && this_logs+=("${logs[i]}")
    done
    python tools/check_test_durations.py "${this_logs[@]}" \
      --ceiling "${MXTPU_TEST_CEILING:-300}" \
      --report tests/TIMINGS.txt || rc=1
  fi
  return $rc
}

run_native() {
  make -C mxnet_tpu/src
  python -m pytest tests/test_native.py tests/test_kvstore_dist.py -x -q
}

run_predict() {
  make -C mxnet_tpu/src c_predict
  python -m pytest tests/test_c_predict.py tests/test_c_train.py -x -q
}

run_predict_native() {
  # Python-free deployment: .mxa AOT export + PJRT C API runtime
  # (predict AND train artifacts — the C client trains without Python)
  make -C mxnet_tpu/src c_predict_native
  python -m pytest tests/test_predict_native.py tests/test_train_native.py -x -q
}

run_entry() {
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -c "import __graft_entry__ as g; g.entry(); g.dryrun_multichip(8); print('entry ok')"
  # driver-robustness variant: TPU plugin stays visible (JAX_PLATFORMS unset,
  # not inherited); dryrun_multichip must force the CPU platform itself
  env -u JAX_PLATFORMS XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('entry ok (tpu visible)')"
  # docs/operators.md is generated — fail if it drifted from the registry
  python tools/gen_op_docs.py
  git diff --exit-code docs/operators.md
  # docs/api_python.md is generated — fail if it drifted from the code
  # (ls-files guards against the file being untracked, where git diff
  # would silently pass)
  git ls-files --error-unmatch docs/api_python.md >/dev/null
  python tools/gen_api_docs.py
  git diff --exit-code docs/api_python.md
  # docs/c_api_coverage.md likewise (needs the built C libs + the reference
  # checkout; the tool skips cleanly when either is absent)
  make -C mxnet_tpu/src c_predict c_predict_native
  python tools/c_api_coverage.py --check
}

run_faults() {
  # fault-injection / robustness tier (docs/fault_tolerance.md): crash-safe
  # checkpoints, engine error propagation, KVStore retry + dead-node
  # handling, all driven deterministically through mxnet_tpu/fault.py.
  # Host-only (no accelerator) and fast; the dist cases need the native lib
  # (run_native builds it) and skip cleanly when it is absent.
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_fault_tolerance.py \
    -q -m "not slow"
}

run_telemetry() {
  # runtime-telemetry tier (docs/observability.md): registry semantics under
  # concurrent writers, Prometheus/chrome-trace exposition (incl. the
  # metric/doc drift gate + trace-event schema validation), fit-loop
  # step/data-wait metrics, KV retry counters under fault injection, the
  # MXNET_TELEMETRY_FILE end-to-end flusher case, and the cluster
  # observability plane (trace identity, cluster_stats, straggler, mxtop,
  # trace_merge smoke). The two slow e2e acceptance scenarios (merged
  # multi-lane trace from a killed-worker run; delayed worker named within
  # 5 steps) run only when this stage is invoked directly, like `elastic`.
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_telemetry.py \
    tests_tpu/test_cluster_obs.py tests_tpu/test_compileobs.py \
    -q -m "not slow"
  if [ "${1:-}" = "with_slow" ]; then
    make -C mxnet_tpu/src
    JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_cluster_obs.py \
      -q -m "slow and telemetry"
  fi
}

run_serving() {
  # serving tier (docs/serving.md): paged-attention numerics vs the
  # contiguous-cache decoder (Pallas kernel in interpret mode = the same
  # program the TPU runs), KV block-pool alloc/free/OOM invariants,
  # continuous-batching FCFS fairness + recompute preemption, the
  # graph-level cache-overflow contract on both decode paths, and the
  # compile-flat-after-warmup gate — plus the observability plane
  # (tests/test_serving_obs.py): phase-clock attribution closure,
  # two-engine stats isolation, SLO burn edge, and the serve.py HTTP
  # schemas — plus the prefix-sharing KV reuse plane
  # (tests/test_serving_prefix.py): refcount/COW invariants,
  # eviction-gain victim picking, sharing bit-identity — and the
  # speculative-decoding plane (tests/test_serving_spec.py):
  # multi-query verify numerics and the greedy-acceptance bit-identity
  # contract — and the resilience plane
  # (tests/test_serving_resilience.py): deadlines/cancellation
  # freeing KV blocks (pool invariant), overload shed + Retry-After,
  # supervised warm restart bit-identical to a fault-free oracle,
  # permanent-failure classification, drain semantics, and the serving
  # fault points (dispatch_error/kv_oom/slow_step). The slow cases
  # (>=32 concurrent variable-length HTTP streams through
  # tools/serve.py, outputs bit-identical to sequential decoding, with
  # and without spec+sharing; the waterfall-attribution e2e; the chaos
  # e2e — injected dispatch fault under concurrent HTTP load → warm
  # supervised restart + SIGTERM drain exit 0) run only when this
  # stage is invoked directly, like `elastic`.
  JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
    tests/test_serving_obs.py tests/test_serving_prefix.py \
    tests/test_serving_spec.py tests/test_serving_resilience.py \
    -q -m "not slow"
  if [ "${1:-}" = "with_slow" ]; then
    JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
      tests/test_serving_obs.py tests/test_serving_prefix.py \
      tests/test_serving_spec.py \
      tests/test_serving_resilience.py -q -m slow
  fi
}

run_pipeline() {
  # input-pipeline feed tier (docs/perf.md §pipeline): uint8-wire numeric
  # parity vs fp32 wire, double-buffer teardown safety, MXNET_FEED_DEPTH,
  # pipeline stage telemetry, and the native C++ decode stage (PIL-oracle
  # parity, quarantine budget, resume/reshard round-trips, fallback
  # counters). Host-only (no accelerator) and fast.
  #
  # The native build gets a graceful skip: on a bare container (no
  # toolchain / no libjpeg) the suite still runs — the stage-specific
  # cases skip themselves and the fallback-counter cases prove the Python
  # path takes over (io.native_decode_fallback stays always-on).
  if ! make -C mxnet_tpu/src >/tmp/mxtpu_pipeline_build.log 2>&1; then
    echo "pipeline tier: native build unavailable (see" \
         "/tmp/mxtpu_pipeline_build.log); running Python-path cases only"
  fi
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_pipeline_feed.py \
    tests_tpu/test_native_decode.py -q -m "not slow"
}

run_perf() {
  # communication-overlap perf tier (docs/distributed.md
  # §communication-overlap): the pure bucket-planner/meter units plus the
  # fast overlap smoke — a 2-worker local dist fit asserting
  # kv.overlap_seconds > 0, per-bucket push counters matching the bucket
  # plan, and final params bit-identical to the monolithic
  # MXNET_KV_BUCKET_MB=0 A/B (classic AND hybrid-fused dist step). The
  # slow case (bit-identity through a mid-epoch worker kill + elastic
  # rejoin) runs only when this stage is invoked directly, like `elastic`.
  make -C mxnet_tpu/src
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_kv_overlap.py \
    -q -m "not slow"
  if [ "${1:-}" = "with_slow" ]; then
    JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_kv_overlap.py \
      -q -m "slow and perf"
  fi
}

run_guard() {
  # training health-guard tier (docs/fault_tolerance.md §health-guard):
  # NaN/stall sentinel, skip/rollback/abort policy ladder, iterator position
  # protocol, exact mid-epoch resume determinism — all via fault injection.
  # Host-only (no accelerator); the multi-rollback end-to-end case is
  # slow-marked and stays out of the blocking tier's timing budget.
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_guard.py \
    -q -m "not slow"
}

run_elastic() {
  # elastic-membership tier (docs/distributed.md §elasticity): membership
  # epoch rejection, registry formation/lapse/rejoin, deterministic
  # epoch-scoped resharding, launcher exit-code/supervisor contract. The
  # kill→reconfigure→rejoin end-to-end cycle (multi-process CPU mesh under
  # tools/launch.py --elastic) is slow-marked; "all" runs the fast set and
  # this stage runs BOTH when invoked directly.
  make -C mxnet_tpu/src
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_elastic.py \
    -q -m "not slow"
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_elastic.py \
    -q -m "slow and elastic"
}

run_server_ha() {
  # parameter-server HA tier (docs/distributed.md §server-HA): replicated
  # group planning + routing, sticky primary promotion, stats wire v2,
  # durable optimizer-slot checkpoints (CRC-corrupt cold start), registry
  # failover off server 0, the dead-server stats penalty window, and the
  # kill_server fault point. The SIGKILL-a-primary → promote-backup →
  # relaunch-rejoins e2e (multi-process CPU mesh under launch.py
  # --elastic) is slow-marked; "all" runs the fast set and this stage
  # runs BOTH when invoked directly.
  make -C mxnet_tpu/src
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_server_ha.py \
    -q -m "not slow"
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_server_ha.py \
    -q -m "slow and server_ha"
}

run_compiler() {
  # compiler tier (docs/compiler.md): graph-pass golden semantics
  # (identity/chain/const folding, CSE merge rules, fusion annotation,
  # the MXNET_GRAPH_PASSES ladder, binding-surface fallback), pass-vs-
  # no-pass numerical parity on zoo models, digest stability, and the
  # compile-cache key/marker/artifact store incl. corrupt-entry
  # fallback + the AOT wrapper lane. The slow cases (cross-process
  # warm-start e2e over two fresh interpreters; resnet/transformer
  # parity) run only when this stage is invoked directly, like `elastic`.
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_graphpass.py \
    -q -m "not slow"
  if [ "${1:-}" = "with_slow" ]; then
    JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_graphpass.py \
      -q -m "slow and compiler"
  fi
}

run_lint() {
  # framework-invariant analyzer (docs/static_analysis.md): AST + dataflow
  # checkers for the repo's hard-won invariants (env parsing, thread/lock
  # hygiene, swallowed exceptions, device escapes in the step path, trace
  # purity, recompile hazards, whole-repo lock ordering). Ratchet: the
  # committed baseline freezes existing debt; only NEW violations fail.
  # Prints per-rule counts; the machine-readable report lands at
  # /tmp/fwlint_report.json (the CI artifact). Stdlib-only (no jax
  # import) and <10s.
  python tools/fwlint.py --baseline ci/fwlint_baseline.json \
    --json-out /tmp/fwlint_report.json
  # the analysis suite: checker positives/negatives, dataflow propagation,
  # suppression + ratchet semantics, engine dependency-sanitizer modes,
  # concurrency rules + lock-order witness modes
  JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_analysis.py \
    -q -m "not slow"
  run_witness_smoke
}

run_witness_smoke() {
  # runtime lock-order witness smoke (docs/static_analysis.md
  # §concurrency): warn mode over one nested pair — the proxy wraps,
  # the edge records, and the always-on lock.* counters move. Stdlib +
  # telemetry only; no jax import on this path.
  JAX_PLATFORMS=cpu python - <<'PYEOF'
import threading
from mxnet_tpu.analysis import witness
from mxnet_tpu import telemetry

witness.configure("warn")
a = witness.declare("ci.smoke.A", threading.Lock())
b = witness.declare("ci.smoke.B", threading.Lock())
with a:
    with b:
        pass
assert ("ci.smoke.A", "ci.smoke.B") in witness.observed_edges()
assert telemetry.histogram(witness.HELD_HISTOGRAM, lock="ci.smoke.A").count == 1
with b:
    with a:  # inversion: counted in warn mode, never raises
        pass
assert telemetry.counter(witness.COUNTER_ORDER).value == 1
witness.configure(None)
raw = threading.Lock()
assert witness.declare("ci.smoke.off", raw) is raw
print("witness smoke ok")
PYEOF
}

run_deep() {
  # non-blocking deep stage: the slow-marked deep-model one-step compiles
  # (e.g. Inception-ResNet-v2) — ~15 min of XLA compile wall on a 1-core
  # host, excluded from `unit` so its 300s per-test ceiling holds
  python -m pytest tests/ -q -m slow --durations=10
}

run_package() {
  # installable-package leg (reference: python/setup.py + tools/pip_package):
  # build a wheel (with the prebuilt native libs), pip-install it into a
  # clean venv, and run the import+fit smoke from OUTSIDE the checkout.
  # jax/numpy come from the invoking interpreter's site-packages via
  # PYTHONPATH (no network in CI; mxnet_tpu is NOT installed there, so the
  # wheel still proves itself); --no-deps proves the wheel, not resolution.
  local workdir repo sitepkgs
  repo="$PWD"
  workdir=$(mktemp -d)
  # set -e exits this function on any failure: clean the workdir (a full
  # venv + wheel) either way
  # shellcheck disable=SC2064
  trap "rm -rf '$workdir'" RETURN
  # purelib AND platlib: numpy/jaxlib are C extensions and land in platlib
  # on split-lib systems
  sitepkgs=$(python -c "import sysconfig; p = sysconfig.get_paths(); \
print(':'.join(dict.fromkeys([p['purelib'], p['platlib']])))")
  python -m pip wheel . --no-deps --no-build-isolation -w "$workdir/dist"
  python -m venv "$workdir/venv"
  "$workdir/venv/bin/pip" install --no-deps --force-reinstall -q \
    "$workdir"/dist/mxnet_tpu-*.whl
  (cd "$workdir" \
     && MXTPU_CHECKOUT="$repo" JAX_PLATFORMS=cpu PYTHONPATH="$sitepkgs" \
        "$workdir/venv/bin/python" "$repo/ci/package_smoke.py")
}

run_tpu() {
  # the device-consistency sweep (reference: tests/python/gpu/): the
  # operator/module/model/attention/rnn/core suites re-executed under the
  # TPU default context. Needs hardware; REQUIRE_HW makes a missing TPU a
  # hard failure instead of a skip. The virtual CPU devices coexist with the
  # chip so multi-device (mesh/fused-Module) cases run inside the sweep too.
  MXNET_TPU_REQUIRE_HW=1 XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests_tpu/ -q
}

run_examples() {
  # smoke-run every example at its smallest configuration (reference CI's
  # tests/python/train + example notebooks axis). Opt-in: each script
  # pays a fresh compile.
  local fast=(
    "train_imagenet.py --num-epochs 1 --num-examples 64 --batch-size 16 --num-classes 10 --num-layers 18"
    "train_ssd.py --num-epochs 1 --num-examples 32 --batch-size 8"
    "train_mnist.py --num-epochs 1"
    "train_cifar10.py --num-epochs 1"
    "train_lm.py --num-epochs 1 --seq-len 32 --num-layers 1"
    "lstm_bucketing.py --num-epochs 1"
    "dcgan.py --num-epochs 1 --steps-per-epoch 4"
    "adversary_fgsm.py --num-epochs 1"
    "memcost.py"
    "profiler_example.py --iters 2"
    "model_parallel_lstm.py"
    "matrix_factorization.py --num-epoch 1"
    "cnn_text_classification.py --num-epoch 1"
    "nce_loss.py --num-epoch 1"
    "svm_mnist.py --num-epoch 1"
    "multi_task.py --num-epoch 1"
    "bi_lstm_sort.py --num-epoch 1"
    "autoencoder.py --num-epoch 1"
    "stochastic_depth.py --num-epoch 1"
    "ocr_ctc.py --num-epoch 1"
    "rcnn_proposal.py"
    "numpy_ops.py --num-epoch 1"
    "fcn_segmentation.py --num-epoch 1"
    "generate_text.py --num-epochs 1 --gen-len 4"
    "dec_clustering.py --pretrain-epochs 2 --refine-iters 5"
    "train_lm_parallel.py --mode sp --devices 2 --steps 3 --seq-len 32 --model-dim 32 --ffn-dim 64 --num-layers 2"
    "reinforcement_learning.py --episodes 10 --max-steps 50"
    "neural_style.py --steps 5 --size 32"
    "speech_demo.py --num-epochs 1 --seq-len 20"
    "kaggle_ndsb.py --num-epochs 1 --size 24"
    "caffe_import.py --num-epoch 1"
    "bayesian_sgld.py --num-epoch 25 --burn-in 10"
    "torch_interop.py --steps 60"
  )
  local failed=0
  for inv in "${fast[@]}"; do
    echo "=== examples/$inv"
    if ! PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
         python examples/${inv} >/tmp/example_ci.log 2>&1; then
      echo "FAILED: $inv (tail of log:)"; tail -5 /tmp/example_ci.log; failed=1
    fi
  done
  # the C++ training example (cpp-package surface; needs the native lib)
  echo "=== examples/cpp/lenet"
  if ! (make -C examples/cpp >/tmp/example_ci.log 2>&1 \
        && cd examples/cpp \
        && PYTHONPATH="$OLDPWD${PYTHONPATH:+:$PYTHONPATH}" JAX_PLATFORMS=cpu \
           ./lenet >>/tmp/example_ci.log 2>&1); then
    echo "FAILED: cpp/lenet (tail of log:)"; tail -5 /tmp/example_ci.log
    failed=1
  fi
  return $failed
}

case "$stage" in
  unit) run_unit ;;
  native) run_native ;;
  compiler) run_compiler with_slow ;;
  faults) run_faults ;;
  telemetry) run_telemetry with_slow ;;
  pipeline) run_pipeline ;;
  perf) run_perf with_slow ;;
  guard) run_guard ;;
  elastic) run_elastic ;;
  server_ha) run_server_ha ;;
  serving) run_serving with_slow ;;
  lint) run_lint ;;
  deep) run_deep ;;
  predict) run_predict ;;
  predict_native) run_predict_native ;;
  entry) run_entry ;;
  tpu) run_tpu ;;
  examples) run_examples ;;
  package) run_package ;;
  all) run_lint; run_native; run_predict; run_predict_native; run_entry;
       run_package; run_faults; run_telemetry; run_pipeline; run_perf;
       run_guard; run_compiler;
       JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_elastic.py -q -m "not slow";
       JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_server_ha.py -q -m "not slow";
       run_unit --ignore=tests/test_native.py --ignore=tests/test_kvstore_dist.py \
                --ignore=tests/test_c_predict.py --ignore=tests/test_predict_native.py \
                --ignore=tests/test_train_native.py ;;
  *) echo "unknown stage: $stage (unit|native|compiler|faults|telemetry|pipeline|perf|guard|elastic|server_ha|serving|lint|deep|predict|predict_native|entry|tpu|examples|package|all)"; exit 2 ;;
esac
