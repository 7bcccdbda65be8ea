#!/usr/bin/env bash
# CI entry point — the single command rounds/reviewers run to validate the
# tree (the reference pins its matrix in .buildkite/gen-pipeline.sh; this
# is the same intent for one TPU/CPU host).
#
#   ./ci.sh            # full: build + lint + tests + dryrun
#   ./ci.sh --fast     # inner loop: quick-marked tests only (~minutes;
#                      # the whole of tests/ is 9,000 CPU-seconds,
#                      # ~21 min with six workers on eight cores)
#   ./ci.sh --chaos    # build + the fault-injection / failure-
#                      # containment suite only (SIGKILL/SIGSTOP gangs,
#                      # deadline bounds, abort metrics)
#   ./ci.sh --lint     # cross-language contract linter only (~1 s, no
#                      # build): C API parity, stats-slot ABI, event
#                      # kinds / frame flags, env-var docs coverage
#   ./ci.sh --sanitize # TSan + UBSan engine builds + the sanitizer
#                      # gang suite (one command instead of the
#                      # hand-assembled HVT_CORE_LIB/LD_PRELOAD dance).
#                      # The TSan gang and the ASan and UBSan replays of
#                      # the fuzz corpus are marked `slow`: they run here
#                      # and in the full run, not under `-m 'not slow'`;
#                      # the shm-against-ring timing of
#                      # tests/test_engine_scaling.py, `slow` too, runs
#                      # in the full run only
#   ./ci.sh --loadtest # build + a tiny loopback ReplicaGang replay
#                      # (horovod_tpu.serving.loadgen --smoke) + the
#                      # artifact schema check
#   ./ci.sh --perfgate # build + perf-regression gate: loopback sweep +
#                      # flight-recorded gang, analyzed and diffed
#                      # against benchmarks/perf_baseline.json (fails
#                      # on >2x p50 regressions; band overridable via
#                      # HVT_PERFGATE_MAX_RATIO)
#   ./ci.sh --perfgate-rebaseline  # refresh the committed baseline
#   ./ci.sh --scale    # build + the simulated-gang control-plane
#                      # harness at a small rank count (star vs tree
#                      # over loopback) + the artifact schema check
#   ./ci.sh --codec    # build + a quick wire-codec sweep over a faked
#                      # 2-host gang (every registry codec, exact byte
#                      # counters + relerr + EF convergence A/B) +
#                      # schema --check of the fresh AND committed
#                      # benchmarks/r09_codec_sweep.json artifacts
#   ./ci.sh --soak     # build + the self-healing chaos campaign
#                      # (benchmarks/soak_transient.py + the reconnect
#                      # gang suite): seeded randomized transient
#                      # faults over a 4-proc gang, asserting
#                      # bit-identical results and zero aborts
#   ./ci.sh --servesoak # build + the serving gang suite (batching
#                      # determinism, lane-pool parity) + an 8-rank
#                      # mixed-tenant serving soak smoke (chaos + host
#                      # kill + autoscaler re-shard over MiniEngine
#                      # workers) + schema/claim --check of the fresh
#                      # AND committed benchmarks/r15_serving_soak.json
#   ./ci.sh --elastic  # build + the checkpointless-recovery gangs
#                      # (kill-a-rank peer rebuild + restart-from-
#                      # checkpoint baseline over a REAL ElasticDriver)
#                      # + a 16-rank kill-a-host smoke capture and
#                      # schema --check of the fresh AND committed
#                      # benchmarks/r14_elastic_recovery.json
#   ./ci.sh --uring    # build + a quick transport-level link-backend
#                      # A/B (tcp vs io_uring ping-pong through the
#                      # PumpDuplex seam, syscalls-per-step column) +
#                      # claim --check of the fresh AND committed
#                      # benchmarks/r18_uring_sweep.json artifacts
#   ./ci.sh --obs      # build + the fleet-telemetry smoke: an 8-rank
#                      # direct-vs-leader-aggregated push pair over a
#                      # live /statusz rendezvous server, incl. the
#                      # hvt_top --once --json round-trip, plus schema
#                      # --check of the fresh AND committed
#                      # benchmarks/r13_telemetry_scaling.json
#   ./ci.sh --fuzz     # wire-protocol lane: the hvt_lint proto pass
#                      # (grammar extraction gate), a UBSan decoder
#                      # build, the seeded deterministic frame-fuzz
#                      # campaign (fixed mutant count per decoder
#                      # family) and the committed tests/corpus replay
#                      # through hvt_decode_probe
#
# Stages:
#   1. build the C++ core engine (csrc -> libhvt_core.so) + the clang
#      -Wthread-safety `tidy` gate (skips when clang is absent)
#   2. contract lint (hvt_lint; also emits the C-API symbol list the
#      nm export check consumes)
#   3. full test suite (8-device virtual CPU mesh; includes the
#      multi-process engine/launcher/elastic integration suites)
#   4. driver multi-chip dryrun: dp/sp/tp + MoE ep + GPipe pp on an
#      8-device mesh with exact single-device parity checks
set -euo pipefail
cd "$(dirname "$0")"

FAST=0
CHAOS=0
SANITIZE=0
LOADTEST=0
PERFGATE=0
REBASELINE=0
SCALE=0
CODEC=0
SOAK=0
OBS=0
ELASTIC=0
SERVESOAK=0
URING_LANE=0
FUZZ=0
[[ "${1:-}" == "--fast" ]] && FAST=1
[[ "${1:-}" == "--chaos" ]] && CHAOS=1
[[ "${1:-}" == "--sanitize" ]] && SANITIZE=1
[[ "${1:-}" == "--loadtest" ]] && LOADTEST=1
[[ "${1:-}" == "--perfgate" ]] && PERFGATE=1
[[ "${1:-}" == "--perfgate-rebaseline" ]] && REBASELINE=1
[[ "${1:-}" == "--scale" ]] && SCALE=1
[[ "${1:-}" == "--codec" ]] && CODEC=1
[[ "${1:-}" == "--soak" ]] && SOAK=1
[[ "${1:-}" == "--obs" ]] && OBS=1
[[ "${1:-}" == "--elastic" ]] && ELASTIC=1
[[ "${1:-}" == "--servesoak" ]] && SERVESOAK=1
[[ "${1:-}" == "--uring" ]] && URING_LANE=1
[[ "${1:-}" == "--fuzz" ]] && FUZZ=1

if [[ "${1:-}" == "--lint" ]]; then
  # pure text analysis — no build, no jax session, ~1 s
  python -m horovod_tpu.tools.hvt_lint
  echo "CI OK (lint)"
  exit 0
fi

# Hard wall-clock guard around every pytest stage: a failure-containment
# regression must FAIL CI (timeout rc 124), never stall it — the gang
# tests hold raw subprocesses that a hung collective would otherwise
# park forever.
PYTEST_GUARD_SEC=${PYTEST_GUARD_SEC:-3600}
run_pytest() {
  timeout -k 30 "$PYTEST_GUARD_SEC" python -m pytest "$@"
}

echo "=== [1/4] build C++ engine ==="
make -C horovod_tpu/csrc -j
make -C horovod_tpu/csrc tf_ops   # no-op when TF is not importable
make -C horovod_tpu/csrc tidy    # clang -Wthread-safety (skips w/o clang)

# Post-build link smoke check: the seed shipped a .so with an unresolved
# shm_open that silently skipped every engine test until PR 1 (see
# CHANGES.md NOTE). A dlopen via ctypes catches load-time breakage;
# `ldd -r` catches lazily-bound undefined symbols dlopen won't touch.
CORE_SO=horovod_tpu/csrc/build/libhvt_core.so
python -c "import ctypes; ctypes.CDLL('$CORE_SO'); print('ctypes load OK')"
if command -v ldd >/dev/null 2>&1; then
  UNDEF=$(ldd -r "$CORE_SO" 2>&1 | grep -i "undefined symbol" || true)
  if [[ -n "$UNDEF" ]]; then
    echo "FATAL: undefined symbols in $CORE_SO:" >&2
    echo "$UNDEF" >&2
    exit 1
  fi
  echo "ldd -r OK (no undefined symbols)"
fi

# The rebuilt .so must export the full C API surface — a stale build
# dir can silently serve an old .so whose missing symbols make the
# Python bridge degrade to zeros. The symbol list comes from the lint's
# c_api.cc parse (single source of truth), so adding a C API in a
# future PR can never silently skip this check.
REQUIRED_SYMS="$(python -m horovod_tpu.tools.hvt_lint --emit-symbols)"
[[ -n "$REQUIRED_SYMS" ]] || { echo "FATAL: --emit-symbols came back empty" >&2; exit 1; }
# snapshot nm once: `nm | grep -q` under pipefail races SIGPIPE (grep -q
# exits on first match, nm dies 141, the pipeline "fails" on a hit)
NM_OUT="$(nm -D "$CORE_SO" 2>/dev/null || true)"
for sym in $REQUIRED_SYMS; do
  if ! grep -q " T $sym\$" <<<"$NM_OUT"; then
    echo "FATAL: $CORE_SO does not export $sym (stale build?)" >&2
    exit 1
  fi
done
echo "C API symbol check OK ($(echo $REQUIRED_SYMS | wc -w) symbols)"

# io_uring kernel-capability probe (PR 18): decides whether the chaos /
# soak lanes can run their specs under BOTH link backends. A failed
# probe (old kernel, seccomp, container policy) is not an error — the
# engine falls back to tcp and the io_uring arms are skipped.
URING_OK=$(python -c "from horovod_tpu.engine import native; \
print(1 if native.uring_supported() else 0)")
if [[ "$URING_OK" == "1" ]]; then
  echo "io_uring kernel probe: supported (chaos/soak run both backends)"
else
  echo "io_uring kernel probe: unsupported (tcp-only)"
fi

if [[ "$CHAOS" == "1" ]]; then
  echo "=== [2/2] chaos / failure-containment suite ==="
  run_pytest tests/test_failure_containment.py \
    tests/test_transport_backends.py -q
  if [[ "$URING_OK" == "1" ]]; then
    echo "--- chaos pass 2: HVT_LINK_BACKEND=io_uring ---"
    HVT_LINK_BACKEND=io_uring run_pytest \
      tests/test_failure_containment.py -q
  fi
  echo "CI OK (chaos)"
  exit 0
fi

if [[ "$SOAK" == "1" ]]; then
  echo "=== [2/3] self-healing reconnect gang suite ==="
  # the session-layer specs are parameterized over both link backends
  # inside the suite (io_uring variants self-skip on a failed probe)
  run_pytest tests/test_self_healing.py -q
  echo "=== [3/3] seeded transient-fault soak ==="
  ART=$(mktemp /tmp/hvt_soak_XXXX.json)
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    python benchmarks/soak_transient.py --rounds 4 --out "$ART"
  echo "soak artifact: $ART"
  if [[ "$URING_OK" == "1" ]]; then
    echo "--- soak pass 2: HVT_LINK_BACKEND=io_uring ---"
    ART2=$(mktemp /tmp/hvt_soak_uring_XXXX.json)
    HVT_LINK_BACKEND=io_uring timeout -k 30 "$PYTEST_GUARD_SEC" \
      python benchmarks/soak_transient.py --rounds 2 --out "$ART2"
    echo "io_uring soak artifact: $ART2"
  fi
  echo "CI OK (soak)"
  exit 0
fi

if [[ "$URING_LANE" == "1" ]]; then
  echo "=== [2/2] link-backend sweep smoke (transport-level A/B) ==="
  ART=$(mktemp /tmp/hvt_uring_XXXX.json)
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    python benchmarks/engine_scaling.py --uring --quick --out "$ART"
  python benchmarks/engine_scaling.py --check "$ART"
  # the committed artifact must also still satisfy its claim gates
  python benchmarks/engine_scaling.py --check \
    benchmarks/r18_uring_sweep.json
  echo "CI OK (uring)"
  exit 0
fi

if [[ "$FUZZ" == "1" ]]; then
  echo "=== [2/4] wire-protocol grammar gate (hvt_lint proto) ==="
  python -m horovod_tpu.tools.hvt_lint proto
  echo "=== [3/4] UBSan decoder build ==="
  make -C horovod_tpu/csrc ubsan
  FUZZ_CORE="$PWD/horovod_tpu/csrc/build-ubsan/libhvt_core.so"
  UBSAN_LIB="$(gcc -print-file-name=libubsan.so 2>/dev/null || true)"
  FUZZ_ENV=()
  if [[ "$UBSAN_LIB" == /* && -e "$UBSAN_LIB" ]]; then
    # halt_on_error: any UB report inside a decoder aborts the
    # campaign — a typed rejection must come from C++ control flow,
    # never from UB that happened to not crash
    FUZZ_ENV=(LD_PRELOAD="$UBSAN_LIB"
              UBSAN_OPTIONS="halt_on_error=1 print_stacktrace=1")
  else
    echo "WARN: libubsan not found — campaign runs on the" \
         "uninstrumented production build" >&2
    FUZZ_CORE="$PWD/horovod_tpu/csrc/build/libhvt_core.so"
  fi
  echo "=== [4/4] deterministic frame-fuzz campaign + corpus replay ==="
  # fixed mutant count + fixed seed: the lane is byte-reproducible, a
  # red run replays exactly with the same command
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    env HVT_CORE_LIB="$FUZZ_CORE" "${FUZZ_ENV[@]}" \
    python -m horovod_tpu.tools.hvt_fuzz --campaign 2500 --seed 20 \
    --replay tests/corpus/proto_frames.jsonl
  echo "CI OK (fuzz)"
  exit 0
fi

if [[ "$PERFGATE" == "1" || "$REBASELINE" == "1" ]]; then
  echo "=== [2/2] perf-regression gate ==="
  if [[ "$REBASELINE" == "1" ]]; then
    timeout -k 30 "$PYTEST_GUARD_SEC" python benchmarks/perf_gate.py \
      --rebaseline
    echo "CI OK (perfgate baseline refreshed — commit benchmarks/perf_baseline.json)"
    exit 0
  fi
  # fixed path, kept after the run: on a FAILED gate this is exactly
  # the report the developer needs to inspect (a mktemp name would
  # leak per failure and scroll out of view)
  ART=/tmp/hvt_perfgate_report.json
  timeout -k 30 "$PYTEST_GUARD_SEC" python benchmarks/perf_gate.py \
    --out "$ART"
  # ratio-based bands (default 2x on p50s, HVT_PERFGATE_MAX_RATIO to
  # override) — generous enough for a shared box, tight enough that a
  # real data/control-plane regression cannot land green
  python -m horovod_tpu.tools.hvt_analyze --diff \
    benchmarks/perf_baseline.json "$ART"
  echo "CI OK (perfgate; report kept at $ART)"
  exit 0
fi

if [[ "$SERVESOAK" == "1" ]]; then
  echo "=== [2/3] serving gang suite (batching + lane pool) ==="
  run_pytest tests/test_serving.py -q
  echo "=== [3/3] 8-rank mixed-tenant serving soak + artifact checks ==="
  # chaos (flaky_conn + partition) + one host SIGKILL + autoscaler
  # re-shard over MiniEngine workers; --check gates the claims
  # (mode-aware: the smoke runs looser timing bounds than the
  # committed 64-rank capture — see benchmarks/serving_soak.py)
  ART=$(mktemp /tmp/hvt_servesoak_XXXX.json)
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    python benchmarks/serving_soak.py --smoke --out "$ART"
  python benchmarks/serving_soak.py --check "$ART"
  # the committed 64-rank artifact must stay schema- and claim-valid
  python benchmarks/serving_soak.py --check \
    benchmarks/r15_serving_soak.json
  rm -f "$ART"
  echo "CI OK (servesoak)"
  exit 0
fi

if [[ "$ELASTIC" == "1" ]]; then
  echo "=== [2/3] checkpointless-recovery gang suite ==="
  # 4-proc fault-injected kill + respawn-rebuild, the restore
  # baseline, and the artifact gates — real ElasticDriver + rendezvous,
  # featherweight MiniEngine workers
  run_pytest tests/test_elastic_recovery.py -q -m "not slow"
  echo "=== [3/3] 16-rank kill-a-host smoke capture + artifact checks ==="
  ART=$(mktemp /tmp/hvt_elastic_XXXX.json)
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    python benchmarks/elastic_recovery.py --smoke --out "$ART"
  python benchmarks/elastic_recovery.py --check "$ART"
  # the committed 128-rank artifact must stay schema-valid too
  python benchmarks/elastic_recovery.py --check \
    benchmarks/r14_elastic_recovery.json
  rm -f "$ART"
  echo "CI OK (elastic)"
  exit 0
fi

if [[ "$OBS" == "1" ]]; then
  echo "=== [2/2] fleet-telemetry smoke (direct vs leader-aggregated) ==="
  # 8-rank / 2-host pair over a live /statusz rendezvous server. Byte
  # metrics are workload-determined, so the reduction claim is stable
  # on a loaded box; the run itself asserts the hvt_top --once --json
  # round-trip and the clean-gang (no-alerts) pin, and --check gates
  # both on the fresh AND the committed artifact. The committed
  # benchmarks/r13_telemetry_scaling.json comes from the full 64-rank
  # --capture matrix — see CHANGES.md PR 13.
  ART=$(mktemp /tmp/hvt_telemetry_XXXX.json)
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    python benchmarks/telemetry_scaling.py --smoke --out "$ART"
  python benchmarks/telemetry_scaling.py --check "$ART"
  python benchmarks/telemetry_scaling.py --check \
    benchmarks/r13_telemetry_scaling.json
  rm -f "$ART"
  echo "CI OK (obs)"
  exit 0
fi

if [[ "$SCALE" == "1" ]]; then
  echo "=== [2/2] control-plane scaling smoke (simulated gangs) ==="
  # star-vs-tree pair at a small rank count over loopback; byte metrics
  # are workload-determined, so the smoke is stable on a loaded box.
  # The committed artifact (benchmarks/r08_controlplane_scaling.json)
  # comes from the full --capture matrix — see CHANGES.md PR 8.
  ART=$(mktemp /tmp/hvt_ctrlscale_XXXX.json)
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    python benchmarks/ctrl_plane_scaling.py --smoke --out "$ART"
  python benchmarks/ctrl_plane_scaling.py --check "$ART"
  # the committed artifact must stay schema-valid too
  python benchmarks/ctrl_plane_scaling.py --check \
    benchmarks/r08_controlplane_scaling.json
  rm -f "$ART"
  echo "CI OK (scale)"
  exit 0
fi

if [[ "$CODEC" == "1" ]]; then
  echo "=== [2/2] wire-codec sweep smoke (faked 2-host gang) ==="
  # quick mode: one size per codec plane + a short convergence A/B.
  # Byte counters are workload-determined (exact), so the reduction
  # claims are stable even on a loaded box; only the p50 columns are
  # noisy, and --check never gates on those. The committed artifact
  # (benchmarks/r09_codec_sweep.json) comes from the full sweep — see
  # CHANGES.md PR 9.
  ART=$(mktemp /tmp/hvt_codecsweep_XXXX.json)
  timeout -k 30 "$PYTEST_GUARD_SEC" \
    python benchmarks/engine_scaling.py --codec --quick --out "$ART"
  python benchmarks/engine_scaling.py --check "$ART"
  # the committed artifact must stay schema-valid too
  python benchmarks/engine_scaling.py --check \
    benchmarks/r09_codec_sweep.json
  rm -f "$ART"
  echo "CI OK (codec)"
  exit 0
fi

if [[ "$LOADTEST" == "1" ]]; then
  echo "=== [2/2] serving loadtest smoke (loopback ReplicaGang) ==="
  # bounded like every pytest stage: a wedged lane must fail CI, not
  # park it (see PYTEST_GUARD_SEC above)
  ART=$(mktemp /tmp/hvt_loadtest_XXXX.json)
  timeout -k 30 "${PYTEST_GUARD_SEC}" env JAX_PLATFORMS=cpu \
    python -m horovod_tpu.runner.launch -np 4 --master-port 29631 \
    python -m horovod_tpu.serving.loadgen --smoke --replicas 2 \
    --window 8 --burst 2 --sync-every 8 --output "$ART"
  python -m horovod_tpu.serving.loadgen --check "$ART"
  rm -f "$ART"
  echo "CI OK (loadtest)"
  exit 0
fi

if [[ "$SANITIZE" == "1" ]]; then
  echo "=== [2/2] sanitizer suite (TSan + UBSan gangs) ==="
  SAN_LOG=$(mktemp)
  run_pytest tests/test_sanitizers.py -q -ra 2>&1 | tee "$SAN_LOG"
  # skip-if-unavailable must not make the gate vacuous: at least one
  # sanitizer gang has to have actually run (gcc<11 skips TSan, a
  # missing libubsan would skip UBSan — all-skipped means nothing was
  # checked, which is a failed gate, not a green one)
  if ! grep -qE "[1-9][0-9]* passed" "$SAN_LOG"; then
    echo "FATAL: no sanitizer test actually ran (all skipped?)" >&2
    rm -f "$SAN_LOG"
    exit 1
  fi
  rm -f "$SAN_LOG"
  echo "CI OK (sanitize)"
  exit 0
fi

echo "=== [2/4] contract lint ==="
python -m horovod_tpu.tools.hvt_lint

echo "=== [3/4] test suite ==="
if [[ "$FAST" == "1" ]]; then
  # quick subset: modules outside tests/conftest.py's known-slow list
  # (subprocess gangs, TF imports, pallas interpret). Full suite stays
  # the round gate.
  run_pytest tests/ -x -q -m quick
else
  run_pytest tests/ -x -q
fi

echo "=== [4/4] multi-chip dryrun (8 virtual devices) ==="
JAX_PLATFORMS=cpu python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

echo "CI OK"
