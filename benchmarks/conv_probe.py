#!/usr/bin/env python
"""Per-shape convolution roofline probe (ResNet-50 MFU investigation).

This probe measures the rate each ResNet-50 conv SHAPE reaches,
fwd-only. The step-level MFU is a blend —
attribution needs per-shape rates: if the 3-channel stem runs at a few
TFLOP/s while the 3x3 body convs run near the matmul ceiling, the stem
is the lever (→ ``ResNet(conv0_space_to_depth=True)``); if the
small-spatial deep convs lag, the
ceiling story is HBM/arithmetic-intensity instead.

Protocol: K independent convs per timed block (stacked inputs walked by
lax.scan, means accumulated into the carry so nothing is dead-code
eliminated), each timed region ending in block_until_ready.
Prints one JSON line per shape.
"""

import json
import sys
import time

K = 4  # independent convs per timed block
REPS = 5


def probe_shape(name, in_shape, w_shape, strides, padding):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(K, *in_shape), jnp.bfloat16)
    w = jnp.asarray(rng.randn(*w_shape), jnp.bfloat16)

    def body(acc, x):
        y = lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return acc + jnp.mean(y.astype(jnp.float32)), None

    @jax.jit
    def block(xs, w):
        acc, _ = lax.scan(body, jnp.float32(0), xs)
        return acc

    out = lax.conv_general_dilated(
        jnp.zeros(in_shape, jnp.bfloat16), w, window_strides=strides,
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    b, ho, wo, co = out.shape
    kh, kw, ci, _ = w_shape
    flops = 2.0 * b * ho * wo * co * kh * kw * ci

    jax.block_until_ready(block(xs, w))  # compile + settle
    rates = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(block(xs, w))
        dt = time.perf_counter() - t0
        rates.append(K * flops / dt / 1e12)
    rec = {"probe": "conv", "name": name, "in": list(in_shape),
           "w": list(w_shape), "strides": list(strides),
           "gflop": round(flops / 1e9, 2),
           "tflops_median": round(float(np.median(rates)), 2),
           "tflops_all": [round(r, 1) for r in rates]}
    print(json.dumps(rec))
    sys.stdout.flush()
    return rec


def main():
    import jax

    platform = jax.devices()[0].platform
    small = platform == "cpu"
    bs = 4 if small else 128
    res = 32 if small else 224
    r2, r4, r8, r32 = res // 2, res // 4, res // 8, res // 32

    shapes = [
        # the 3-input-channel stem, standard vs space-to-depth form
        ("stem_7x7_s2", (bs, res, res, 3), (7, 7, 3, 64), (2, 2),
         ((3, 3), (3, 3))),
        ("stem_s2d_4x4", (bs, r2, r2, 12), (4, 4, 12, 64), (1, 1),
         ((2, 1), (2, 1))),
        # body convs, one per stage (stage-1 spatial = res/4)
        ("s1_1x1_64", (bs, r4, r4, 64), (1, 1, 64, 64), (1, 1),
         ((0, 0), (0, 0))),
        ("s1_1x1_expand", (bs, r4, r4, 64), (1, 1, 64, 256), (1, 1),
         ((0, 0), (0, 0))),
        ("s1_3x3_64", (bs, r4, r4, 64), (3, 3, 64, 64), (1, 1),
         ((1, 1), (1, 1))),
        ("s2_3x3_128", (bs, r8, r8, 128), (3, 3, 128, 128), (1, 1),
         ((1, 1), (1, 1))),
        ("s3_3x3_256", (bs, r8 // 2, r8 // 2, 256), (3, 3, 256, 256),
         (1, 1), ((1, 1), (1, 1))),
        ("s4_3x3_512", (bs, r32, r32, 512), (3, 3, 512, 512), (1, 1),
         ((1, 1), (1, 1))),
    ]
    recs = [probe_shape(*s) for s in shapes]
    stem = next(r for r in recs if r["name"] == "stem_7x7_s2")
    s2d = next(r for r in recs if r["name"] == "stem_s2d_4x4")
    # The two stems produce the SAME outputs but execute different FLOP
    # counts (s2d's zero-padded taps: 4*4*12=192 vs 7*7*3=147 MACs per
    # output), so the honest comparison is wall-time per block, not
    # TFLOP/s: time = gflop / tflops.
    speedup = None
    if stem["tflops_median"] and s2d["tflops_median"]:
        t_std = stem["gflop"] / stem["tflops_median"]
        t_s2d = s2d["gflop"] / s2d["tflops_median"]
        speedup = round(t_std / t_s2d, 2)
    print(json.dumps({"probe": "conv_summary", "platform": platform,
                      "stem_s2d_time_speedup": speedup}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
