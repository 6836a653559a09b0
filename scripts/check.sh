#!/usr/bin/env bash
# patrol-check: the repo-wide static-analysis + sanitizer + prover gate.
#
# One command, one pass/fail exit code, ten stages (plus one opt-in):
#
#   lint    — repo-specific AST checks over patrol_tpu/ (clock seams,
#             jit-reachable sync primitives, lock order, nanotoken dtype
#             discipline; patrol_tpu/analysis/lint.py) plus their
#             fixture-driven self-tests (pytest -m lint).
#   tidy    — clang-tidy with the curated native profile (.clang-tidy)
#             over patrol_tpu/native/. Skipped with a notice when
#             clang-tidy is not installed (the container images don't
#             ship LLVM).
#   san     — TSan, ASan (+LSan), and UBSan builds of BOTH multi-threaded
#             drivers: scripts/tsan_driver.cpp (UDP/codec/directory plane)
#             and scripts/san_http_driver.cpp (epoll front, h1 parser, h2
#             frame machine, hls_take_locked, HostStore mutex, hostile
#             inputs). Any sanitizer report fails the run.
#   prove   — patrol-prove: the jaxpr-level CRDT invariant prover
#             (patrol_tpu/analysis/prove.py, scripts/prove_repo.py): the
#             structural lattice check + exhaustive small-domain model
#             check over every registered kernel root, plus the
#             pytest -m prove fixture self-tests.
#   abi     — patrol-abi: the native-ABI conformance prover + cross-
#             boundary concurrency lint (patrol_tpu/analysis/abi.py,
#             scripts/abi_repo.py): pt_fold_hybrid / pt_rx_classify
#             driven through ctypes over the prove lattice domains and
#             checked bit-exact against the registered jax kernel roots
#             (incl. the merge laws on the native side), the host-lane
#             store schedule explorer, and the NATIVE_EFFECTS
#             completeness check; plus the pytest -m abi self-tests.
#             Skips LOUDLY (exit 77) when libpatrolhost cannot build.
#   protocol— patrol-protocol: the bounded replication-protocol model
#             checker (patrol_tpu/analysis/protocol.py,
#             scripts/protocol_repo.py): enumerates bounded 2-3 node
#             cluster schedules (takes × drop/dup/reorder/partition/heal)
#             against a step-for-step protocol model and machine-checks
#             convergence-after-heal, state monotonicity, the AP bound
#             admitted <= limit × partition_sides, and dup/reorder
#             idempotence (PTC001-004) — with seeded protocol mutations
#             (e.g. resync-overwrites-instead-of-joins) demonstrably
#             rejected (PTC005); plus the pytest -m protocol self-tests.
#             Pure python, never skips.
#   race    — patrol-race: the cross-seam concurrency prover + guarded-
#             state static analysis (patrol_tpu/analysis/race.py,
#             scripts/race_repo.py): exhaustive deterministic
#             interleavings of the epoll-seam protocol model
#             (pt_http_poll park/drain, completion-ring (slot, gen)
#             tags) checking lost wakeups and completion-ring token
#             conservation (PTR001-002, 4 seeded mutations rejected),
#             plus the guarded-state / lock-graph / condvar-predicate /
#             buffer-ownership AST passes over the engine/net thread
#             ensemble (PTR003-005); and the pytest -m race self-tests.
#             Pure python, never skips.
#   lin     — patrol-lin: replication-aware linearizability checking
#             against a sequential token-bucket spec
#             (patrol_tpu/analysis/linearizability.py,
#             scripts/lin_repo.py): every kernel family registered in
#             ops/obligations.py::LIN_SPECS is run through the SHARED
#             stage-6 schedule enumerator plus a sync-delivery suite,
#             with every take checked for justification under explicit
#             per-node visibility (PTN001-004: per-node soundness,
#             visibility-respecting linearization, sync-schedule
#             exactness, no manufactured grants) and seeded lin
#             mutations demonstrably rejected with their exact codes
#             (PTN005); plus the pytest -m lin self-tests.
#             Pure python, never skips.
#   cert    — patrol-cert: the kernel-certification meta-check
#             (patrol_tpu/analysis/cert.py, scripts/cert_repo.py) over
#             the declarative KernelFamily registry
#             (ops/obligations.py::KERNEL_FAMILIES): every lattice
#             family reachable by every applicable stage — prove /
#             protocol / lin / bench — or justified-exempt (PTK001),
#             every seeded mutation demonstrably rejected with its
#             exact code, mutant kernels and family-law payloads
#             executed in-process (PTK002), every declared-absent
#             obligation justified (PTK003), every module-level *_jit
#             kernel under ops/ registered (PTK004), and registry
#             integrity (PTK005); plus the pytest -m cert self-tests.
#             CPU-pinned jax models, never skips.
#   dispatch— patrol-dispatch: the dispatch-discipline prover +
#             compile-cache stability witness
#             (patrol_tpu/analysis/dispatch.py, scripts/dispatch_repo.py)
#             over the declared DispatchSpec registry
#             (ops/obligations.py::DISPATCH_SPECS): retrace-risk AST
#             dataflow at the engine jit call sites incl. shape-bucket
#             law drift (PTD001), donation discipline incl.
#             use-after-donate (PTD002), implicit host transfers on the
#             serve graph (PTD003), a deterministic witness that warms
#             every registered hot path then re-drives it at identical
#             shapes under a compile counter + the jax device-to-host
#             transfer guard (PTD004), and witness completeness over
#             every engine-dispatched jitted kernel (PTD005) — with
#             seeded mutations demonstrably rejected with their exact
#             codes; plus the pytest -m dispatch self-tests.
#             CPU-pinned jax, never skips.
#   asan-py — OPT-IN (never in the default set; select explicitly with
#             --stage): the ctypes-facing pytest subset under
#             LD_PRELOAD=libasan with an ASan-instrumented
#             libpatrolhost.so (PATROL_NATIVE_LIB), leak-checking
#             callback lifetimes and numpy buffer ownership across
#             pt_http_poll. Skips with a notice when the toolchain lacks
#             a preloadable libasan.
#
# Stage selection:   check.sh --stage lint,prove     # <10 s fast path
#                    check.sh --stage asan-py        # the opt-in seam check
# The final line is machine-readable so an outer CI can assert that no
# stage silently skipped (scripts/ci_gate.sh does exactly that):
#                    PATROL_CHECK stages=10 pass=9 skip=1 fail=0 skipped=tidy failed=-
#
# Prereqs and the lint/prove suppression format are documented in
# README.md ("patrol-check").
set -euo pipefail
cd "$(dirname "$0")/.."

DEFAULT_STAGES="lint,tidy,san,prove,abi,protocol,race,lin,cert,dispatch"
STAGES="$DEFAULT_STAGES"
while [[ $# -gt 0 ]]; do
  case "$1" in
    --stage|--stages) STAGES="$2"; shift 2 ;;
    --stage=*|--stages=*) STAGES="${1#*=}"; shift ;;
    -h|--help)
      sed -n '2,99p' "$0" | sed 's/^# \{0,1\}//'
      exit 0 ;;
    *) echo "unknown argument: $1 (try --stage lint,tidy,san,prove,abi,protocol,race,lin,cert,dispatch,asan-py)" >&2
       exit 2 ;;
  esac
done
[[ "$STAGES" == "all" ]] && STAGES="$DEFAULT_STAGES"

have_pytest() { python -c "import pytest" >/dev/null 2>&1; }

# ---------------------------------------------------------------------------
# Toolchain probe (carried hygiene item): gcc-10's libtsan cannot
# intercept pthread_cond_clockwait, which forced the
# wait_until(system_clock) workaround into pt_http_poll, and its ASan
# CHECK-fails on jaxlib's static __cxa_throw, degrading asan-py to the
# non-jit subset. gcc >= 12 (or clang >= 14 as the sanitizer compiler)
# fixes both: the san stage then builds with -DPT_STEADY_CV_WAIT, which
# reverts pt_http_poll to the steady-clock cv wait_for, and the asan-py
# jax probe comes back full. One notice line either way so the state of
# the workaround is never silent.
GXX_MAJOR=$(g++ -dumpversion 2>/dev/null | cut -d. -f1 || echo 0)
CLANG_MAJOR=$(clang --version 2>/dev/null | grep -oE 'version [0-9]+' | grep -oE '[0-9]+' | head -1 || true)
SAN_CV_FLAGS=""
if [[ "${GXX_MAJOR:-0}" -ge 12 || "${CLANG_MAJOR:-0}" -ge 14 ]]; then
  SAN_CV_FLAGS="-DPT_STEADY_CV_WAIT"
  echo "patrol-check: toolchain probe: g++ ${GXX_MAJOR:-?} / clang ${CLANG_MAJOR:--} — modern sanitizers:" \
       "reverting the wait_until(system_clock) TSan workaround (steady-clock cv wait)" \
       "and expecting the full asan-py jit subset"
else
  echo "patrol-check: toolchain probe: g++ ${GXX_MAJOR:-?} / clang ${CLANG_MAJOR:--} — pre-12/14 sanitizers:" \
       "keeping the wait_until(system_clock) TSan workaround; asan-py degrades to the" \
       "non-jit subset (see ROADMAP toolchain-blocked hygiene)"
fi

# Each stage runs in a subshell with its own `set -e`; exit 77 = skipped.

stage_lint() (
  set -euo pipefail
  echo "== patrol-check [lint] AST lint over patrol_tpu/ =="
  python scripts/lint_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_lint.py -q -m lint \
      -p no:cacheprovider
  else
    echo "pytest unavailable: lint self-tests skipped (lint itself ran)"
  fi
)

stage_tidy() (
  set -euo pipefail
  echo "== patrol-check [tidy] clang-tidy (patrol_tpu/native/) =="
  if ! command -v clang-tidy >/dev/null 2>&1; then
    echo "clang-tidy not installed: SKIPPED (needs LLVM >= 14; see README.md)"
    exit 77
  fi
  clang-tidy --version | head -2
  clang-tidy \
    patrol_tpu/native/patrol_host.cpp \
    patrol_tpu/native/patrol_http.cpp \
    -- -std=c++17 -x c++ -DPT_NO_MAIN
  echo "clang-tidy: clean"
)

stage_san() (
  set -euo pipefail
  echo "== patrol-check [san] sanitizer drivers =="
  OUT=$(mktemp -d)
  trap 'rm -rf "$OUT"' EXIT

  build_and_run() {
    local san="$1" driver="$2" extra="" runenv=""
    case "$san" in
      thread)    extra="";                         runenv="TSAN_OPTIONS=halt_on_error=1" ;;
      address)   extra="";                         runenv="ASAN_OPTIONS=halt_on_error=1:detect_leaks=1" ;;
      undefined) extra="-fno-sanitize-recover=all" runenv="UBSAN_OPTIONS=halt_on_error=1:print_stacktrace=1" ;;
    esac
    local srcs bin="$OUT/${driver}_${san}"
    case "$driver" in
      host) srcs="scripts/tsan_driver.cpp patrol_tpu/native/patrol_host.cpp" ;;
      http) srcs="scripts/san_http_driver.cpp patrol_tpu/native/patrol_host.cpp patrol_tpu/native/patrol_http.cpp" ;;
    esac
    echo "-- $driver driver / $san --"
    # SAN_CV_FLAGS (toolchain probe above) reverts the TSan condvar
    # workaround on toolchains whose libtsan intercepts clockwait.
    # shellcheck disable=SC2086
    g++ -std=c++17 -O1 -g -fsanitize="$san" $extra $SAN_CV_FLAGS -fPIC -o "$bin" \
        $srcs -DPT_NO_MAIN -lpthread -ldl
    env "$runenv" "$bin"
  }

  for san in thread address undefined; do
    build_and_run "$san" host
    build_and_run "$san" http
  done
)

stage_prove() (
  set -euo pipefail
  echo "== patrol-check [prove] jaxpr CRDT invariant prover =="
  python scripts/prove_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_prove.py -q -m prove \
      -p no:cacheprovider
  else
    echo "pytest unavailable: prove self-tests skipped (prover itself ran)"
  fi
)

stage_abi() (
  set -euo pipefail
  echo "== patrol-check [abi] native-ABI conformance prover =="
  # abi_repo.py exits 77 itself when libpatrolhost cannot load — the
  # stage skips LOUDLY instead of vacuously passing.
  python scripts/abi_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_abi.py -q -m abi \
      -p no:cacheprovider
  else
    echo "pytest unavailable: abi self-tests skipped (prover itself ran)"
  fi
)

stage_protocol() (
  set -euo pipefail
  echo "== patrol-check [protocol] bounded replication-protocol model checker =="
  python scripts/protocol_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_protocol.py -q -m protocol \
      -p no:cacheprovider
  else
    echo "pytest unavailable: protocol self-tests skipped (checker itself ran)"
  fi
)

stage_race() (
  set -euo pipefail
  echo "== patrol-check [race] cross-seam concurrency prover + guarded-state analysis =="
  python scripts/race_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_race.py -q -m race \
      -p no:cacheprovider
  else
    echo "pytest unavailable: race self-tests skipped (prover itself ran)"
  fi
)

stage_lin() (
  set -euo pipefail
  echo "== patrol-check [lin] replication-aware linearizability checker =="
  python scripts/lin_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_lin.py -q -m lin \
      -p no:cacheprovider
  else
    echo "pytest unavailable: lin self-tests skipped (checker itself ran)"
  fi
)

stage_cert() (
  set -euo pipefail
  echo "== patrol-check [cert] kernel-certification meta-check =="
  python scripts/cert_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_cert.py -q -m cert \
      -p no:cacheprovider
  else
    echo "pytest unavailable: cert self-tests skipped (meta-check itself ran)"
  fi
)

stage_dispatch() (
  set -euo pipefail
  echo "== patrol-check [dispatch] dispatch-discipline prover + compile-cache witness =="
  python scripts/dispatch_repo.py
  if have_pytest; then
    env JAX_PLATFORMS=cpu python -m pytest tests/test_dispatch.py -q -m dispatch \
      -p no:cacheprovider
  else
    echo "pytest unavailable: dispatch self-tests skipped (prover itself ran)"
  fi
)

stage_asan_py() (
  set -euo pipefail
  echo "== patrol-check [asan-py] ctypes seam under LD_PRELOAD=libasan =="
  local_asan=$(gcc -print-file-name=libasan.so 2>/dev/null || true)
  if [[ "$local_asan" != /* || ! -e "$local_asan" ]]; then
    echo "no preloadable libasan.so (gcc -print-file-name): SKIPPED"
    exit 77
  fi
  if ! have_pytest; then
    echo "pytest unavailable: SKIPPED"
    exit 77
  fi
  OUT=$(mktemp -d)
  trap 'rm -rf "$OUT"' EXIT
  echo "-- building ASan-instrumented libpatrolhost --"
  g++ -std=c++17 -O1 -g -shared -fPIC -fsanitize=address -pthread \
      -o "$OUT/libpatrolhost_asan.so" \
      patrol_tpu/native/patrol_host.cpp patrol_tpu/native/patrol_http.cpp
  # malloc_context_size keeps native allocation stacks within native
  # frames, so the interpreter-side LSan suppressions cannot mask a real
  # native leak (scripts/lsan_python.supp).
  ASAN_PY_ENV=(
    LD_PRELOAD="$local_asan"
    PATROL_NATIVE_LIB="$OUT/libpatrolhost_asan.so"
    ASAN_OPTIONS="halt_on_error=1:detect_leaks=1:malloc_context_size=5:detect_odr_violation=0"
    LSAN_OPTIONS="suppressions=scripts/lsan_python.supp:print_suppressions=0"
    JAX_PLATFORMS=cpu
  )
  # gcc-10's ASan CHECK-fails on __cxa_throw from jaxlib's statically
  # linked MLIR bindings, killing any test that TRACES jax under the
  # preload. Probe once; on a broken toolchain run the non-jit ctypes
  # seam (codec/socket/directory) and say exactly what was dropped.
  SUBSET=(tests/test_native.py tests/test_native_http.py tests/test_native_hls.py)
  DESELECT=()
  if ! env "${ASAN_PY_ENV[@]}" ASAN_OPTIONS="detect_leaks=0" \
      python -c "import jax; jax.jit(lambda x: x + 1)(1)" >/dev/null 2>&1; then
    echo "NOTICE: this toolchain's ASan cannot host jax tracing" \
         "(gcc-10 __cxa_throw interceptor vs jaxlib's static libstdc++);"
    echo "NOTICE: running the non-jit ctypes seam only (tests/test_native.py" \
         "codec/socket/directory, minus the engine-driven TestRxDedup);" \
         "the pt_http_poll seam needs gcc >= 12 / llvm asan."
    SUBSET=(tests/test_native.py)
    DESELECT=(-k "not TestRxDedup")
  fi
  env "${ASAN_PY_ENV[@]}" \
      python -m pytest "${SUBSET[@]}" ${DESELECT[@]+"${DESELECT[@]}"} \
        -q -p no:cacheprovider
)

PASS=() ; SKIP=() ; FAIL=()
run_stage() {
  local name="$1" fn="$2" rc=0
  "$fn" || rc=$?
  case "$rc" in
    0)  PASS+=("$name") ;;
    77) SKIP+=("$name") ;;
    *)  FAIL+=("$name"); echo "patrol-check: stage '$name' FAILED (rc=$rc)" >&2 ;;
  esac
}

IFS=',' read -r -a SELECTED <<<"$STAGES"
for s in "${SELECTED[@]}"; do
  case "$s" in
    lint|tidy|san|prove|abi|protocol|race|lin|cert|dispatch|asan-py) ;;
    *) echo "unknown stage: '$s' (valid: lint tidy san prove abi protocol race lin cert dispatch asan-py)" >&2; exit 2 ;;
  esac
done
for s in lint tidy san prove abi protocol race lin cert dispatch asan-py; do
  for sel in "${SELECTED[@]}"; do
    if [[ "$sel" == "$s" ]]; then
      case "$s" in
        lint)    run_stage lint    stage_lint ;;
        tidy)    run_stage tidy    stage_tidy ;;
        san)     run_stage san     stage_san ;;
        prove)   run_stage prove   stage_prove ;;
        abi)     run_stage abi     stage_abi ;;
        protocol) run_stage protocol stage_protocol ;;
        race)    run_stage race    stage_race ;;
        lin)     run_stage lin     stage_lin ;;
        cert)    run_stage cert    stage_cert ;;
        dispatch) run_stage dispatch stage_dispatch ;;
        asan-py) run_stage asan-py stage_asan_py ;;
      esac
    fi
  done
done

total=$(( ${#PASS[@]} + ${#SKIP[@]} + ${#FAIL[@]} ))
join() { local IFS=','; [[ $# -gt 0 ]] && echo "$*" || echo "-"; }
echo "PATROL_CHECK stages=$total pass=${#PASS[@]} skip=${#SKIP[@]} fail=${#FAIL[@]} skipped=$(join ${SKIP[@]+"${SKIP[@]}"}) failed=$(join ${FAIL[@]+"${FAIL[@]}"})"
if [[ ${#FAIL[@]} -gt 0 ]]; then
  exit 1
fi
echo "patrol-check: ALL CLEAN"
