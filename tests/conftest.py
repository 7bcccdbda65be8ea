"""Test harness: force an 8-device virtual CPU platform so multi-chip
sharding/collectives are exercised without TPU hardware (the same trick the
driver's dryrun uses: ``--xla_force_host_platform_device_count``)."""

import os
import signal
import sys

# Must happen before the first jax backend initialization.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Tests run on the virtual CPU mesh whatever the host offers (a chip host
# exports JAX_PLATFORMS itself).
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Build artifacts are not committed; (re)build the C++ engine once per test
# session so the multi-process suites run.
_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "horovod_tpu", "csrc")


def _ensure_engine_built():
    import fcntl
    import subprocess

    # Every xdist worker imports this file. The first to hold the lock
    # builds; the others wait here and then find the library fresh.
    with open(os.path.join(_CSRC, "Makefile")) as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        lib = os.path.join(_CSRC, "build", "libhvt_core.so")
        stamp = os.path.getmtime(lib) if os.path.exists(lib) else 0
        sources = [os.path.join(_CSRC, f) for f in os.listdir(_CSRC)
                   if f.endswith((".cc", ".h")) or f == "Makefile"]
        if sources and stamp < max(os.path.getmtime(s) for s in sources):
            result = subprocess.run(["make", "-C", _CSRC, "-j"],
                                    capture_output=True, text=True,
                                    timeout=600)
            if result.returncode != 0:
                raise RuntimeError(
                    f"C++ engine build failed:\n{result.stdout}\n"
                    f"{result.stderr}")
        # TF custom-op library (optional; skipped inside make when TF
        # absent). Worth the one-time compile: it unlocks the in-graph TF
        # parallel suite.
        tf_lib = os.path.join(_CSRC, "build", "libhvt_tf_ops.so")
        tf_src = os.path.join(_CSRC, "tf_ops.cc")
        if os.path.exists(tf_src) and (
                not os.path.exists(tf_lib)
                or os.path.getmtime(tf_lib) < os.path.getmtime(tf_src)):
            subprocess.run(["make", "-C", _CSRC, "tf_ops"],
                           capture_output=True, text=True, timeout=600)


_ensure_engine_built()


# ------------------------------------------------------- the long modules
# The modules below take most of the suite's time (multi-subprocess gangs,
# TF imports per worker, pallas interpret mode, heavy 8-device compiles,
# model and reference compiled side by side). The set does two things:
# - everything NOT in it is auto-marked `quick`; `./ci.sh --fast` runs
#   `-m quick` (~minutes). The full suite stays the round gate.
# - its items are collected FIRST. `xdist --dist load` hands items out in
#   collection order, and what remains at the end of a run has to be short
#   cases: by the alphabet alone the TensorFlow and torch gangs start last
#   and one or two workers finish them while the others stand idle.
# Classification is by module because the cost is dominated by
# per-module fixtures (subprocess spawns, TF import, first-compile), not
# individual test bodies. `tests/test_tier1_gate.py` holds every
# name to a file that exists.
_SLOW_MODULES = {
    "test_engine_integration",   # real 2/4/5-process engine gangs
    "test_data_plane",           # 2/4-process ring/wire-codec gangs
    "test_flight_recorder",      # 2-process timeline/stall gangs
    "test_multiprocess_jit",     # jax.distributed subprocess pairs
    "test_engine_scaling",       # timed eager-plane benchmarks
    "test_adasum",               # multi-process numeric cross-checks
    "test_autotune",             # engine cycles to convergence
    "test_tensorflow",           # TF import + eager engine paths
    "test_tensorflow_native",    # TF custom-op gangs (20 s import/worker)
    "test_tensorflow_real",      # real keras fits
    "test_torch_parallel",       # multi-process torch gangs
    "test_examples",             # every example as a subprocess
    "test_ctrl_plane",           # 4/16-process tree/star control gangs
    "test_failure_containment",  # chaos gangs (SIGKILL/SIGSTOP + deadlines)
    "test_elastic_driver",       # launcher + failure/growth scenarios
    "test_elastic_recovery",     # kill-a-rank MiniEngine recovery gangs
    "test_runner",               # launcher subprocesses
    "test_preemption",           # signal/recovery scenarios
    "test_flash_attention",      # pallas interpret mode is slow on CPU
    "test_sequence_parallel",    # ring/ulysses 8-device compiles
    "test_serving",              # 4-proc serving gangs + loadgen replay
    "test_serving_soak",         # mixed-tenant MiniEngine soak smoke
    "test_models_vision",        # ResNet/VGG/Inception init
    "test_models_gpt",           # GPT init + flash and ring paths
    "test_models_hybrid",        # sparse and hybrid models vs references
    "test_models_qwen3_next",    # Qwen3-Next's layers vs the reference
    "test_models_keye",          # the newer families' models and mixers
    "test_models_kimi",          # vs their references: model and
    "test_models_kanana",        # reference compiled side by side
    "test_models_trinity",
    "test_moe", "test_moe_held", "test_gdn", "test_kda", "test_sconv",
    "test_mla",
    "test_gdn_kernel",           # pallas interpret mode, as above
    "test_kda_kernel", "test_head_norm_kernel",
    "test_chip_compile",         # compiles for a described v5e
    "test_sanitizers",           # TSAN/ASAN rebuilds
    "test_self_healing",         # reconnect/replay chaos gangs
    "test_telemetry",            # fault-injected telemetry gangs
    "test_chip_smoke",           # launcher subprocess + 12-layer GPT job
    "test_integrations",         # real gang + HTTP-store suites
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast inner-loop subset (auto-applied to "
                   "modules outside the known-slow list; run with "
                   "`pytest -m quick` or `./ci.sh --fast`)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` "
                   "verify run (multi-minute captures; the full "
                   "./ci.sh suite still runs them)")


def _in_long_module(item):
    return item.module.__name__.rsplit(".", 1)[-1] in _SLOW_MODULES


def pytest_collection_modifyitems(config, items):
    for item in items:
        if not _in_long_module(item):
            item.add_marker(pytest.mark.quick)
    # stable: every worker collects the same order, as xdist requires
    items.sort(key=lambda item: not _in_long_module(item))


# No test may wait longer than this: a gang that hangs would otherwise
# cost the run its whole window and never be named. SIGALRM interrupts
# the main thread's waits (subprocess, locks, sleeps) and the test fails.
_TEST_LIMIT_SEC = 300


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    def waited_too_long(signum, frame):
        pytest.fail(f"{item.nodeid} waited longer than the limit of "
                    f"{_TEST_LIMIT_SEC} s", pytrace=False)

    previous = signal.signal(signal.SIGALRM, waited_too_long)
    signal.setitimer(signal.ITIMER_REAL, _TEST_LIMIT_SEC)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(scope="session", autouse=True)
def _hvt_init():
    import horovod_tpu as hvt

    hvt.init()
    yield


@pytest.fixture()
def world_mesh():
    from horovod_tpu.parallel import mesh

    return mesh.global_mesh()
