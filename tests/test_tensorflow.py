"""TF-binding tests with numpy-level fakes — the binding's core is
framework-agnostic, so these run even without a TF install (the gated
pattern the Ray/Spark suites use). Real-TF coverage lives in
``test_tensorflow_real.py``. Reference API under test:
``tensorflow/__init__.py:396-742`` DistributedOptimizer /
_DistributedGradientTape."""

import numpy as np
import pytest


@pytest.fixture(scope="module", autouse=True)
def _binding():
    """Imported by the worker that runs this file, not by all six at
    collection: the binding imports TensorFlow where it is installed
    (8 to 13 s each)."""
    global hvt_tf, Compression
    import horovod_tpu.tensorflow as hvt_tf
    from horovod_tpu.tensorflow.compression import Compression


class FakeTape:
    """Quacks like tf.GradientTape for .gradient()."""

    def __init__(self, grads):
        self.grads = grads
        self.calls = 0

    def gradient(self, target, sources, output_gradients=None):
        self.calls += 1
        return self.grads


class FakeIndexedSlices:
    def __init__(self, values, indices):
        self.values = np.asarray(values)
        self.indices = np.asarray(indices)


class FakeOptimizer:
    def __init__(self):
        self.applied = []
        self.lr = 0.125  # arbitrary attribute for passthrough checks

    def apply_gradients(self, grads_and_vars, **kwargs):
        self.applied.append(list(grads_and_vars))
        return "applied"


def test_tape_dense_grads_single_process():
    grads = [np.full((3,), 4.0, np.float32), None,
             np.arange(4, dtype=np.float32)]
    tape = hvt_tf.DistributedGradientTape(FakeTape(grads))
    out = tape.gradient("loss", ["a", "b", "c"])
    assert out[1] is None
    np.testing.assert_allclose(out[0], grads[0])  # avg over 1 process
    np.testing.assert_allclose(out[2], grads[2])
    assert tape._tape.calls == 1


def test_tape_single_tensor_and_fp16_compression():
    g = np.full((8,), 3.0, np.float32)
    tape = hvt_tf.DistributedGradientTape(FakeTape(g),
                                          compression=Compression.fp16)
    out = tape.gradient("loss", "w")
    assert not isinstance(out, list)
    assert out.dtype == np.float32  # decompressed back
    np.testing.assert_allclose(out, 3.0)


def test_tape_sparse_grads_roundtrip():
    g = FakeIndexedSlices(np.full((2, 3), 6.0, np.float32), [1, 4])
    out = tape_out = hvt_tf.DistributedGradientTape(
        FakeTape([g])).gradient("loss", ["emb"])[0]
    assert isinstance(tape_out, FakeIndexedSlices)
    np.testing.assert_array_equal(out.indices, [1, 4])
    np.testing.assert_allclose(out.values, 6.0)  # avg over 1 process


def test_optimizer_applies_reduced_grads_and_delegates():
    inner = FakeOptimizer()
    opt = hvt_tf.DistributedOptimizer(inner)
    assert opt.lr == 0.125  # attribute passthrough
    g = np.ones((2,), np.float32)
    r = opt.apply_gradients([(g, "var0"), (None, "var1")])
    assert r == "applied"
    (applied,) = inner.applied
    np.testing.assert_allclose(applied[0][0], 1.0)
    assert applied[0][1] == "var0" and applied[1] == (None, "var1")


def test_optimizer_backward_passes_per_step_aggregates():
    inner = FakeOptimizer()
    opt = hvt_tf.DistributedOptimizer(inner, backward_passes_per_step=3)
    g = np.ones((2,), np.float32)
    assert opt.apply_gradients([(g, "v")]) is None
    assert opt.apply_gradients([(2 * g, "v")]) is None
    assert inner.applied == []  # no update during aggregation
    opt.apply_gradients([(3 * g, "v")])
    (applied,) = inner.applied
    np.testing.assert_allclose(applied[0][0], 6.0)  # local sum 1+2+3
    # next cycle starts fresh
    assert opt.apply_gradients([(g, "v")]) is None


def test_optimizer_average_aggregated_gradients():
    inner = FakeOptimizer()
    opt = hvt_tf.DistributedOptimizer(inner, backward_passes_per_step=2,
                                      average_aggregated_gradients=True)
    g = np.ones((2,), np.float32)
    opt.apply_gradients([(g, "v")])
    opt.apply_gradients([(3 * g, "v")])
    (applied,) = inner.applied
    np.testing.assert_allclose(applied[0][0], 2.0)  # (1+3)/2


def test_optimizer_rejects_sparse_with_aggregation():
    import pytest

    opt = hvt_tf.DistributedOptimizer(FakeOptimizer(),
                                      backward_passes_per_step=2)
    s = FakeIndexedSlices(np.ones((1, 2), np.float32), [0])
    with pytest.raises(ValueError, match="sparse"):
        opt.apply_gradients([(s, "emb")])


def test_compression_fp16_roundtrip_and_passthrough():
    c = Compression.fp16
    x = np.linspace(-2, 2, 7, dtype=np.float32)
    comp, ctx = c.compress(x)
    assert comp.dtype == np.float16 and ctx == np.float32
    back = c.decompress(comp, ctx)
    assert back.dtype == np.float32
    np.testing.assert_allclose(back, x, atol=1e-2)
    ints = np.arange(4, dtype=np.int64)
    comp, ctx = c.compress(ints)
    assert comp.dtype == np.int64 and ctx is None


def test_keras_distributed_optimizer_delegates():
    """keras.DistributedOptimizer routes through the eager TF wrapper
    (Keras 3 drives updates through apply_gradients)."""
    import horovod_tpu.keras as hvt_keras

    inner = FakeOptimizer()
    opt = hvt_keras.DistributedOptimizer(inner, backward_passes_per_step=2)
    g = np.ones((2,), np.float32)
    assert opt.apply_gradients([(g, "v")]) is None
    opt.apply_gradients([(g, "v")])
    (applied,) = inner.applied
    np.testing.assert_allclose(applied[0][0], 2.0)
