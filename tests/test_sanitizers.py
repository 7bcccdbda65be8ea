"""Sanitizer smoke over the C++ engine (SURVEY §5.2: sanitizers as a CI
matrix choice; one-command wrapper: ``./ci.sh --sanitize``). Each test
builds the engine with a sanitizer (`make tsan` / `make ubsan`), then
drives a real 2-proc job that hammers the engine from multiple
submitter threads, with the sanitizer runtime preloaded and findings
fatal.

- TSan: any data race in the engine-thread/submitter/waiter interplay
  fails the job via TSAN_OPTIONS exitcode. Cross-PROCESS shm
  synchronization is outside TSan's model; the progress-word design +
  interleave stress tests cover that.
- UBSan: undefined behavior in the wire codec / reduce kernels
  (misaligned loads, overflow, bad enum casts) aborts the job via
  halt_on_error.
- ASan/UBSan fuzz replay: the committed wire-frame corpus
  (tests/corpus/proto_frames.jsonl) plus a deterministic mini-campaign
  runs through hvt_decode_probe under each instrumented build, so a
  decoder bounds bug the grammar fuzzer can reach fails here too.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from tests.test_engine_integration import REPO, _PORT


def _gcc_lib(name):
    try:
        p = subprocess.run(["gcc", "-print-file-name=" + name],
                           capture_output=True, text=True,
                           timeout=60).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return ""
    return p if os.path.isabs(p) and os.path.exists(p) else ""


TSAN_LIB = _gcc_lib("libtsan.so")
UBSAN_LIB = _gcc_lib("libubsan.so")
ASAN_LIB = _gcc_lib("libasan.so")
# co-preloaded with libasan for the fuzz replay: python itself is not
# linked against libstdc++, so without it in the initial library list
# ASan's __cxa_throw interceptor finds no real symbol and aborts on the
# first TruncatedFrameError ("real___cxa_throw != 0" CHECK)
STDCXX_LIB = _gcc_lib("libstdc++.so.6")


def _gcc_major():
    try:
        v = subprocess.run(["gcc", "-dumpversion"], capture_output=True,
                           text=True, timeout=60).stdout.strip()
        return int(v.split(".")[0])
    except (OSError, ValueError, subprocess.SubprocessError):
        return 0


# gcc-10's libtsan mis-tracks mutex lifetime on this image ("mutex is
# already destroyed" on a live, never-destroyed engine mutex), then
# reports every queue_mu_-protected submit/drain access as a race even
# while printing that BOTH threads hold the same write lock. Verified
# pre-existing: the identical report family reproduces on the unmodified
# parent tree. Run the TSan gang only on a libtsan new enough to trust.
TSAN_TRUSTWORTHY = _gcc_major() >= 11

WORKER = textwrap.dedent("""
    import sys, threading
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvt
    hvt.init()
    r, n = hvt.rank(), hvt.size()

    def worker(tid):
        for i in range(25):
            res = np.asarray(hvt.allreduce(
                np.full((64,), float(r + 1), np.float32), op=hvt.Sum,
                name=f"t{{tid}}.{{i}}"))
            np.testing.assert_allclose(
                res, float(sum(k + 1 for k in range(n))))

    ths = [threading.Thread(target=worker, args=(t,)) for t in range(2)]
    [t.start() for t in ths]
    [t.join() for t in ths]
    print(f"rank {{r}}: SANITIZER OK")
""").format(repo=REPO)


def _build_sanitized(target):
    rc = subprocess.run(["make", "-C",
                         os.path.join(REPO, "horovod_tpu", "csrc"),
                         target], capture_output=True, text=True,
                        timeout=900)
    assert rc.returncode == 0, rc.stderr[-2000:]
    return os.path.join(REPO, "horovod_tpu", "csrc",
                        f"build-{target}", "libhvt_core.so")


def _run_sanitized_gang(tmp_path, target, preload, extra_env):
    """Build `make -C csrc <target>` and drive the 2-proc multi-threaded
    gang against it; returns (proc, report_files).

    The sanitizer runtime is preloaded ONLY into the worker processes
    (via an `env LD_PRELOAD=…` wrapper in the worker argv), never into
    the launcher: libtsan's fork interceptors deadlock the launcher's
    multi-threaded spawn path, wedging the whole gang before any worker
    runs — and the launcher is not what the test instruments anyway."""
    core = _build_sanitized(target)
    worker = tmp_path / "w.py"
    worker.write_text(WORKER)
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO,
        "HVT_CORE_LIB": core,
        "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
    })
    env.update(extra_env)
    _PORT[0] += 1
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--master-port", str(_PORT[0]),
         "/usr/bin/env", f"LD_PRELOAD={preload}",
         sys.executable, str(worker)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    reports = [f for f in os.listdir(tmp_path)
               if f.startswith("sanitizer_report")]
    return proc, reports


@pytest.mark.skipif(not TSAN_LIB, reason="libtsan not available")
@pytest.mark.skipif(not TSAN_TRUSTWORTHY,
                    reason="gcc<11 libtsan: known destroyed-mutex "
                           "false positives (see TSAN_TRUSTWORTHY note)")
@pytest.mark.slow  # a cold `make tsan` under six workers; `./ci.sh
#                    --sanitize` is this case's gate, as it is ASan's
@pytest.mark.timeout(600)
def test_engine_threading_clean_under_tsan(tmp_path):
    report = str(tmp_path / "sanitizer_report")
    # halt_on_error off: collect everything, judge by report files +
    # forced exitcode on any finding
    proc, reports = _run_sanitized_gang(
        tmp_path, "tsan", TSAN_LIB,
        {"TSAN_OPTIONS": f"exitcode=66 log_path={report}"})
    assert proc.returncode == 0 and not reports, (
        f"rc={proc.returncode} reports={reports}\n{proc.stdout[-2000:]}"
        f"\n{proc.stderr[-2000:]}")
    assert proc.stdout.count("SANITIZER OK") == 2, proc.stdout[-1000:]


def _run_sanitized_fuzz(tmp_path, target, preload, extra_env):
    """Build `make -C csrc <target>` and replay the committed wire-frame
    corpus — plus a small deterministic grammar-derived campaign — with
    the sanitizer runtime preloaded into hvt_fuzz's decode process.
    Single-process (no gang): every frame goes straight into the decoder
    families via hvt_decode_probe, which is exactly the surface the
    fuzzer exercises."""
    core = _build_sanitized(target)
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HVT_CORE_LIB": core,
                "LD_PRELOAD": preload})
    env.update(extra_env)
    corpus = os.path.join(REPO, "tests", "corpus", "proto_frames.jsonl")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.tools.hvt_fuzz",
         "--replay", corpus, "--campaign", "500", "--seed", "20", "-q"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    reports = [f for f in os.listdir(tmp_path)
               if f.startswith("sanitizer_report")]
    return proc, reports


@pytest.mark.slow  # cold `make asan` is a multi-minute build; `./ci.sh
#                    --sanitize` and the full run are its gate
@pytest.mark.skipif(not ASAN_LIB or not STDCXX_LIB,
                    reason="libasan/libstdc++ not available")
@pytest.mark.timeout(600)
def test_fuzz_corpus_clean_under_asan(tmp_path):
    report = str(tmp_path / "sanitizer_report")
    # detect_leaks off: CPython itself leaks by LSan's definition; the
    # target here is heap overflow/UAF in the decoders, not leaks
    proc, reports = _run_sanitized_fuzz(
        tmp_path, "asan", f"{ASAN_LIB} {STDCXX_LIB}",
        {"ASAN_OPTIONS": f"detect_leaks=0:halt_on_error=1:"
                         f"log_path={report}"})
    assert proc.returncode == 0 and not reports, (
        f"rc={proc.returncode} reports={reports}\n{proc.stdout[-2000:]}"
        f"\n{proc.stderr[-2000:]}")


@pytest.mark.slow  # with the ASan twin above: the build and the replay of
#                    the corpus belong to `./ci.sh --sanitize`
@pytest.mark.skipif(not UBSAN_LIB, reason="libubsan not available")
@pytest.mark.timeout(600)
def test_fuzz_corpus_clean_under_ubsan(tmp_path):
    report = str(tmp_path / "sanitizer_report")
    proc, reports = _run_sanitized_fuzz(
        tmp_path, "ubsan", UBSAN_LIB,
        {"UBSAN_OPTIONS": f"halt_on_error=1 print_stacktrace=1 "
                          f"log_path={report}"})
    assert proc.returncode == 0 and not reports, (
        f"rc={proc.returncode} reports={reports}\n{proc.stdout[-2000:]}"
        f"\n{proc.stderr[-2000:]}")


@pytest.mark.skipif(not UBSAN_LIB, reason="libubsan not available")
@pytest.mark.timeout(600)
def test_engine_clean_under_ubsan(tmp_path):
    report = str(tmp_path / "sanitizer_report")
    # halt_on_error: any UB report (normally print-and-continue) aborts
    # the worker, which the launcher surfaces as a nonzero exit
    proc, reports = _run_sanitized_gang(
        tmp_path, "ubsan", UBSAN_LIB,
        {"UBSAN_OPTIONS": f"halt_on_error=1 print_stacktrace=1 "
                          f"log_path={report}"})
    assert proc.returncode == 0 and not reports, (
        f"rc={proc.returncode} reports={reports}\n{proc.stdout[-2000:]}"
        f"\n{proc.stderr[-2000:]}")
    assert proc.stdout.count("SANITIZER OK") == 2, proc.stdout[-1000:]
