"""Test harness: force an 8-device virtual CPU platform so multi-chip
sharding/collectives are exercised without TPU hardware (the same trick the
driver's dryrun uses: ``--xla_force_host_platform_device_count``)."""

import os
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
    import subprocess

    lib = os.path.join(_CSRC, "build", "libhvt_core.so")
    stamp = os.path.getmtime(lib) if os.path.exists(lib) else 0
    sources = [os.path.join(_CSRC, f) for f in os.listdir(_CSRC)
               if f.endswith((".cc", ".h")) or f == "Makefile"]
    if sources and stamp < max(os.path.getmtime(s) for s in sources):
        result = subprocess.run(["make", "-C", _CSRC, "-j"],
                                capture_output=True, text=True)
        if result.returncode != 0:
            raise RuntimeError(
                f"C++ engine build failed:\n{result.stdout}\n{result.stderr}")
    # TF custom-op library (optional; skipped inside make when TF absent).
    # Worth the one-time compile: it unlocks the in-graph TF parallel suite.
    tf_lib = os.path.join(_CSRC, "build", "libhvt_tf_ops.so")
    tf_src = os.path.join(_CSRC, "tf_ops.cc")
    if os.path.exists(tf_src) and (
            not os.path.exists(tf_lib)
            or os.path.getmtime(tf_lib) < os.path.getmtime(tf_src)):
        subprocess.run(["make", "-C", _CSRC, "tf_ops"],
                       capture_output=True, text=True)


_ensure_engine_built()


# ---------------------------------------------------------------- quick set
# Inner-loop marker: the full suite takes tens of minutes, dominated by
# the modules below (multi-subprocess gangs, TF imports per worker,
# pallas interpret mode, heavy 8-device compiles). Everything NOT in
# this list is auto-marked `quick`;
# `./ci.sh --fast` runs `-m quick` (~minutes). The full suite stays
# the round gate. Classification is by module because the cost is
# dominated by per-module fixtures (subprocess spawns, TF import,
# first-compile), not individual test bodies.
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
    "test_sanitizers",           # TSAN/ASAN rebuilds
    "test_self_healing",         # reconnect/replay chaos gangs
    "test_telemetry",            # fault-injected telemetry gangs
    "test_bench",                # full harness runs
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


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__.rsplit(".", 1)[-1] not in _SLOW_MODULES:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(scope="session", autouse=True)
def _hvt_init():
    import horovod_tpu as hvt

    hvt.init()
    yield


@pytest.fixture()
def world_mesh():
    from horovod_tpu.parallel import mesh

    return mesh.global_mesh()
