"""The ``k``-th largest score of every row (``ops/kth_largest.py``): the
Pallas kernel through the interpreter against ``jax.lax.top_k``'s last
value, repeats counted as ``top_k`` counts them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import kth_largest as kth


def _scores(scene, rows, width):
    x = jax.random.normal(jax.random.key(rows + width), (rows, width))
    if scene == "repeats":          # columns that repeat, rows of one value
        x = x.at[:, 5].set(x[:, 9]).at[:, 17].set(x[:, 9])
        x = x.at[:7].set(0.25).at[10, :width // 2].set(1.0)
    elif scene == "saturated":      # a sigmoid's: most at 0 or at 1
        x = jax.nn.sigmoid(40.0 * x)
    elif scene == "negative":       # a bias can take a score below zero
        x = -jnp.abs(x) - 1.0
    return x


@pytest.mark.parametrize("scene", ["distinct", "repeats", "saturated",
                                   "negative"])
@pytest.mark.parametrize("rows, width, block, k", [
    (256, 128, 128, 1), (256, 128, 128, 22), (256, 128, 256, 128),
    (384, 256, 128, 10), (128, 512, None, 22)])
def test_kernel_gives_top_k_last_value(rows, width, block, k, scene):
    x = _scores(scene, rows, width)
    got = kth.kth_largest(x, k, rows=block)
    want = kth.kth_largest_plain(x, k)
    assert got.shape == (rows, 1) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(want)[:, 0], np.sort(np.asarray(x), -1)[:, -k])


def test_kernel_is_one_jit_and_refuses_a_block_that_does_not_tile():
    x = _scores("distinct", 256, 128)
    text = jax.jit(lambda x: kth.kth_largest(x, 3) + kth.kth_largest(
        2 * x, 3)).lower(x).as_text()
    assert text.count("func.func private @_call") == 1      # shared
    with pytest.raises(ValueError, match="do not tile"):
        kth.kth_largest(x, 3, rows=192)
    with pytest.raises(ValueError, match="do not tile"):
        kth.kth_largest(x[:200], 3, rows=128)


@pytest.mark.parametrize("shape, serves", [
    ((16384, 512, 22), False), ((100, 512, 2), False), ((256, 64, 2), False)])
def test_the_kernel_serves_a_tpu_alone(shape, serves, monkeypatch):
    """Off a TPU nothing goes to the kernel; on one, whole 128-lane tiles
    both ways and a ``k`` the width holds."""
    assert kth.serves(*shape) is serves
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kth.serves(*shape) is (shape == (16384, 512, 22))
    assert not kth.serves(256, 128, 0) and not kth.serves(256, 128, 129)


def test_kernel_counts_its_traces():
    from horovod_tpu import metrics

    def count():
        m = metrics.registry().get("hvt_moe_kth_kernel_traces_total")
        return m.labels(k="4", width="128", rows="128").value if m else 0.0

    before = count()
    jax.jit(lambda x: kth.kth_largest(x, 4, rows=128)).lower(
        jnp.zeros((128, 128)))
    assert count() == before + 1
