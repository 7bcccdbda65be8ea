"""Example smoke tests — the analog of the reference CI running its
examples as smoke jobs (``.buildkite/gen-pipeline.sh:135-173``). Each
example runs as a real subprocess with tiny shapes on the CPU platform;
the multi-process ones go through the actual ``hvtrun`` launcher."""

import os
import subprocess
import sys

import pytest

from tests.test_engine_integration import LIB, REPO, _PORT

TF_OPS_LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build",
                          "libhvt_tf_ops.so")


def _run_example(argv, timeout=300, np_procs=None, extra_env=None):
    env = dict(os.environ)
    # One OpenMP thread a process: with a thread a core in each, two ranks
    # of torch spin against each other and against the other test workers
    # (the ResNet-50 example: 108 s and 387 CPU-seconds, for 16 s and 23).
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                "TF_CPP_MIN_LOG_LEVEL": "3", "OMP_NUM_THREADS": "1",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", "")})
    env.update(extra_env or {})
    if np_procs:
        _PORT[0] += 1
        cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
               "-np", str(np_procs), "--master-port", str(_PORT[0]),
               sys.executable, *argv]
    else:
        cmd = [sys.executable, *argv]
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"{argv}\nrc={proc.returncode}\nstdout:\n{proc.stdout[-3000:]}\n" \
        f"stderr:\n{proc.stderr[-3000:]}"
    return proc.stdout + proc.stderr


def test_jax_synthetic_benchmark_smoke():
    out = _run_example(
        ["examples/jax/jax_synthetic_benchmark.py", "--batch-size", "2",
         "--num-iters", "1", "--num-batches-per-iter", "1",
         "--image-size", "32", "--fp32"])
    assert "img/sec" in out, out[-1000:]


def test_jax_gpt_train_smoke_dp_tp():
    out = _run_example(
        ["examples/jax/jax_gpt_train.py", "--dp", "2", "--tp", "2",
         "--steps", "2", "--batch", "2", "--seq", "32"],
        extra_env={"XLA_FLAGS":
                   "--xla_force_host_platform_device_count=4"})
    assert "loss" in out.lower(), out[-1000:]


@pytest.mark.skipif(not os.path.exists(LIB),
                    reason="C++ engine not built")
def test_torch_synthetic_benchmark_smoke_2proc():
    out = _run_example(
        ["examples/torch/pytorch_synthetic_benchmark.py",
         "--batch-size", "4", "--num-iters", "1",
         "--num-batches-per-iter", "2"], np_procs=2)
    assert "img/sec" in out or "sec" in out, out[-1000:]


@pytest.mark.skipif(not os.path.exists(TF_OPS_LIB),
                    reason="TF op library not built")
def test_tf_function_train_smoke_2proc():
    out = _run_example(["examples/tensorflow/tf_function_train.py"],
                       np_procs=2, timeout=420)
    assert "loss" in out, out[-1000:]


@pytest.mark.skipif(not os.path.exists(TF_OPS_LIB),
                    reason="TF op library not built")
def test_tf_tape_train_smoke_2proc():
    out = _run_example(["examples/tensorflow/tf_tape_train.py"],
                       np_procs=2, timeout=420)
    assert "loss" in out, out[-1000:]


@pytest.mark.skipif(not os.path.exists(TF_OPS_LIB),
                    reason="TF op library not built")
def test_tf_elastic_train_smoke_2proc():
    out = _run_example(["examples/tensorflow/tf_elastic_train.py"],
                       np_procs=2, timeout=420)
    assert "epoch 4" in out, out[-1500:]


@pytest.mark.skipif(not os.path.exists(LIB),
                    reason="C++ engine not built")
def test_torch_imagenet_resnet50_smoke_2proc():
    # fp16 compression + grouped fusion + local aggregation — the
    # BASELINE.json torch-ImageNet config (reference
    # examples/pytorch/pytorch_imagenet_resnet50.py) at smoke scale
    out = _run_example(
        ["examples/torch/pytorch_imagenet_resnet50.py", "--width", "8",
         "--image-size", "32", "--batch-size", "4", "--epochs", "1",
         "--steps-per-epoch", "2", "--batches-per-allreduce", "2"],
        np_procs=2, timeout=420)
    assert "img/sec" in out, out[-1000:]


@pytest.mark.skipif(not os.path.exists(LIB),
                    reason="C++ engine not built")
def test_torch_elastic_train_smoke_2proc():
    # reference examples/elastic/pytorch analog: TorchState +
    # @hvd.elastic.run over the real launcher in elastic mode
    _PORT[0] += 1
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", "")})
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", "2", "--min-np", "2", "--max-np", "3",
           "--master-port", str(_PORT[0]), sys.executable,
           "examples/elastic/pytorch_elastic_train.py",
           "--epochs", "3", "--batches-per-epoch", "2"]
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=420)
    assert proc.returncode == 0, \
        f"rc={proc.returncode}\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    assert "done: epochs=3" in proc.stdout + proc.stderr


@pytest.mark.skipif(not os.path.exists(TF_OPS_LIB),
                    reason="TF op library not built")
def test_keras_mnist_smoke_2proc():
    # BASELINE.json Keras-MNIST config (reference
    # examples/tensorflow2/tensorflow2_keras_mnist.py): full callback
    # set through real model.fit
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        out = _run_example(
            ["examples/keras/keras_mnist.py", "--epochs", "2",
             "--batch-size", "8", "--steps-per-epoch", "2",
             "--checkpoint-dir", d],
            np_procs=2, timeout=420)
    assert "final loss" in out, out[-1500:]


@pytest.mark.skipif(not os.path.exists(TF_OPS_LIB),
                    reason="TF op library not built")
def test_keras_synthetic_benchmark_smoke_2proc():
    # reference tensorflow2_keras_synthetic_benchmark.py analog:
    # tape + DistributedOptimizer.apply_gradients throughput loop
    out = _run_example(
        ["examples/keras/keras_synthetic_benchmark.py", "--small",
         "--batch-size", "4", "--image-size", "32",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
         "--num-iters", "1"],
        np_procs=2, timeout=420)
    assert "Img/sec" in out, out[-1500:]


def test_jax_long_context_train_smoke():
    out = _run_example(
        ["examples/jax/jax_long_context_train.py", "--sp", "4", "--seq",
         "128", "--steps", "4", "--batch", "1", "--fp32"],
        extra_env={"XLA_FLAGS":
                   "--xla_force_host_platform_device_count=4"})
    assert "final loss" in out and "flash=on" in out, out[-1000:]
