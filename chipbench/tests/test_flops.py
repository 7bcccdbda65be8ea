"""``flops.py`` against counts made by hand."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops


def test_gpt2_large_parameters_by_hand():
    # embedding 50257 x 1280; a block: q k v o 4 x 1280^2, up and down
    # 2 x 1280 x 5120, two norm scales; one final norm scale
    embedding = 50257 * 1280
    block = 4 * 1280 * 1280 + 2 * 1280 * 5120 + 2 * 1280
    assert block == 19_663_360
    n = flops.gpt_params(50257, 36, 1280, 5120)
    assert n == embedding + 36 * block + 1280 == 772_211_200


@pytest.mark.parametrize("seq_len, gflop", [(1024, 4.92), (4096, 5.77)])
def test_gpt2_large_flops_per_token(seq_len, gflop):
    got = flops.gpt_train_flops_per_token(772_211_200, 36, 1280, seq_len)
    assert got == 6 * 772_211_200 + 6 * 36 * 1280 * seq_len
    assert round(got / 1e9, 2) == gflop


def test_jaxpr_macs_counts_conv_and_dot_from_shapes():
    def fn(x, k, w):
        y = jax.lax.conv_general_dilated(
            x, k, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.mean(y, (1, 2)) @ w

    shapes = (jax.ShapeDtypeStruct((2, 8, 8, 3), jnp.float32),
              jax.ShapeDtypeStruct((3, 3, 3, 16), jnp.float32),
              jax.ShapeDtypeStruct((16, 10), jnp.float32))
    # conv: 2 x 4 x 4 x 16 outputs, each 3 x 3 x 3 multiply-adds;
    # dense: 2 x 10 outputs, each 16
    assert flops.forward_macs(fn, *shapes) == 2 * 4 * 4 * 16 * 27 + 2 * 10 * 16


def test_resnet50_macs_and_training_flops():
    from chipbench.families import resnet

    config = {"stage_sizes": [3, 4, 6, 3], "width": 64, "num_classes": 1000,
              "dtype": "bfloat16",
              "optimizer": {"name": "sgd", "learning_rate": 0.01}}
    job = resnet.build(config, {"image_size": 224, "per_chip_batch": 2})
    macs = job.facts["forward_macs_per_image"]
    # stem by hand: 112 x 112 x 64 outputs of a 7 x 7 x 3 window
    assert macs > 112 * 112 * 64 * 147
    assert round(macs / 1e9, 3) == 4.089
    assert round(job.flops_per_item / 1e9, 1) == 24.5


def test_flash_calls_by_hand():
    shape = dict(batch=2, heads=20, seq_len=4096, head_dim=64)
    one_matmul = 2 * 4096 * 4096 * 64           # FLOPs, unmasked
    assert flops.flash_call_flops("fwd", **shape) == 40 * 2 * one_matmul / 2
    assert flops.flash_call_flops("dq", **shape) == 40 * 3 * one_matmul / 2
    assert flops.flash_call_flops("dkv", **shape) == 40 * 4 * one_matmul / 2
    # forward: q k v o in bf16 and lse in f32, once each
    assert flops.flash_call_bytes("fwd", **shape) == 40 * 4096 * (
        4 * 64 * 2 + 4)
    peak = flops.peaks("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(
        flops.flash_call_flops("fwd", **shape),
        flops.flash_call_bytes("fwd", **shape), peak)
    assert bound == "compute"
    assert seconds == pytest.approx(85.9e9 / 197e12, rel=1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        flops.peaks("TPU v99")
