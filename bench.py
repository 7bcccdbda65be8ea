#!/usr/bin/env python
"""Synthetic ResNet benchmark — the TPU-native analog of the reference's
``examples/pytorch/pytorch_synthetic_benchmark.py`` (prints img/sec ± stdev;
reference lines :110,:117) and the tf_cnn_benchmarks recipe the reference's
published numbers use (``docs/benchmarks.rst:28-43``).

Data-parallel over the visible chips; the gradient reduction is compiled
into the step (XLA ICI allreduce). Each timed block runs
``--num-batches-per-iter`` training steps inside ONE compiled program
(``lax.fori_loop``) so host dispatch latency is amortized the way a real
TPU input pipeline would.

One process owns the chips: nothing is spawned before JAX is imported, a
run that lands on the CPU without ``--force-cpu`` exits non-zero naming
the platform, and every timed region ends in ``block_until_ready``.

Anchoring (metric-of-record support, BASELINE.md):
- ``mfu_vs_peak``: achieved model FLOP/s (theoretical per-image training
  FLOPs × throughput) over the chip's published bf16 peak, from one
  table keyed by ``device_kind`` (``PEAK_BF16_TFLOPS``); a device that is
  not in the table is an error. XLA's own cost-analysis count is reported
  alongside as ``xla_flops_per_img``.
- ``calib_tflops`` / ``mfu``: a dependent bf16 matmul chain timed through
  the same harness, and achieved FLOP/s over that measured rate.
- ``scaling``: 1→N chip sweep, per-chip efficiency vs the 1-chip run —
  the reference's headline metric (``docs/benchmarks.rst:13-14``).

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...,
   "mfu_vs_peak": ..., "calib_tflops": ..., "achieved_tflops": ...,
   "scaling": ...}

vs_baseline denominator: the reference's only published absolute number,
1656.82 img/sec for ResNet-101 on 16 GPUs (``docs/benchmarks.rst:43``)
= 103.55 img/sec/device.

Keeping fp32 BatchNorm statistics is a deliberate accuracy/parity choice
(the reference's fp16 recipes also keep BN in fp32).
"""

import argparse
import functools
import json
import os
import sys
import time
from typing import NamedTuple

BASELINE_IMG_SEC_PER_DEVICE = 1656.82 / 16.0

# Bump whenever a change makes numbers incomparable with earlier records
# (harness restructure, different measurement protocol, new defaults).
# r6.0: timed regions end in block_until_ready (no forced readback, no
#       implausible-iteration filter, no `suspect` field); the peak comes
#       from PEAK_BF16_TFLOPS by device_kind; a CPU run needs --force-cpu.
HARNESS_VERSION = "r6.0"

# Published bf16 peak per chip, TFLOP/s, keyed by jax's `device_kind`.
# "TPU v5 lite" is the TPU v5e: 197 TFLOP/s bf16 (Google Cloud
# documentation, "TPU v5e"). A device that is missing here is an error,
# not a default: add it with its source.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}

# Theoretical training FLOPs (fwd+bwd+update ≈ 3x forward; ResNet-50 fwd ≈
# 4.1 GFLOP/img @224², ResNet-101 ≈ 7.8) — the MFU numerator.
# Training FLOPs (3x forward, forward = 2x MACs), algorithmic counts at
# the model's native resolution (224; inception_v3 scales from 299).
FLOPS_PER_IMG = {"resnet50": 12.3e9, "resnet101": 23.4e9,
                 "resnet152": 34.5e9, "vgg16": 46.5e9,
                 "inception_v3": 17.1e9}
NATIVE_IMG_SIZE = {"resnet50": 224, "resnet101": 224, "resnet152": 224,
                   "vgg16": 224, "inception_v3": 299}

_REPO = os.path.dirname(os.path.abspath(__file__))


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and nothing is set here; otherwise the cache goes to
    one fixed path in the checkout — the path is part of the cache key,
    so a directory that moves never hits. The only place code names a
    cache directory; ``hvt.init()`` sets none for user programs."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def peak_bf16_tflops(device):
    """Published bf16 peak of ``device`` in TFLOP/s; None on the CPU
    (harness validation has no peak); an unknown accelerator raises."""
    if device.platform == "cpu":
        return None
    if device.device_kind not in PEAK_BF16_TFLOPS:
        raise ValueError(
            f"no published peak for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it to "
            f"bench.PEAK_BF16_TFLOPS with its source")
    return PEAK_BF16_TFLOPS[device.device_kind]


def _compiled_flops(lowered_compiled):
    """Total FLOPs of a compiled executable per XLA's cost analysis, or
    None if the backend doesn't report them."""
    try:
        cost = lowered_compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def matmul_chain(platform):
    """``(chain, x, w, flops)``: a jitted chain of dependent bf16 matmuls
    and the FLOPs of one call — timed by ``calibrate_matmul_tflops`` and
    by chip_smoke.py's check that ``block_until_ready`` waits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    # CPU (test harness validation) can't chew 8192³; keep it tiny there.
    m, k_steps = (8192, 8) if platform != "cpu" else (512, 4)
    x = jnp.asarray(np.random.RandomState(0).randn(m, m), jnp.bfloat16)
    w = jnp.asarray(np.random.RandomState(1).randn(m, m), jnp.bfloat16)

    @jax.jit
    def chain(x, w):
        return lax.fori_loop(0, k_steps, lambda i, h: h @ w, x)

    return chain, x, w, k_steps * 2 * m ** 3


def calibrate_matmul_tflops(platform):
    """Measured bf16 matmul rate: the chain timed through the same
    perf_counter harness as the model benchmark, median of three."""
    import jax
    import numpy as np

    chain, x, w, flops = matmul_chain(platform)
    jax.block_until_ready(chain(x, w))  # compile + settle
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x, w))
        samples.append(flops / (time.perf_counter() - t0) / 1e12)
    return float(np.median(samples))


class TrainJob(NamedTuple):
    """One model set up for the benchmark's train step.
    ``block(*state, *batch)`` runs ``num_batches_per_iter`` steps in ONE
    compiled program (one host dispatch per timed block), donates
    ``state`` and returns ``(*state, loss)``. ``batch`` is sharded over
    ``dp`` and ``state`` replicated; ``loss_fn(..., *batch)`` is the
    scalar loss the step differentiates; ``cfg`` is set for GPT only."""

    block: object
    state: tuple
    batch: tuple
    loss_fn: object
    cfg: object = None


def _multi_step_block(train_step, n_state, num_batches_per_iter, unroll):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def block_fn(*args):
        # The batch is an ARGUMENT: an array the step merely closed over
        # is baked into the program as a replicated constant, and every
        # chip then computes the whole global batch with no all-reduce.
        state, batch = args[:n_state], args[n_state:]
        # unroll > 1 removes the while-loop barrier between consecutive
        # steps so the scheduler can overlap step i's optimizer/stats
        # tail with step i+1's matmuls (A/B lever; see --unroll)
        state, loss = lax.fori_loop(
            0, num_batches_per_iter,
            lambda i, c: train_step(*c[0], *batch),
            (state, jnp.float32(0)), unroll=unroll)
        return (*state, loss)

    return jax.jit(block_fn, donate_argnums=tuple(range(n_state)))


def _time_block(job, num_iters, num_batches_per_iter):
    """Warm ``job.block`` up once, then time ``num_iters`` calls of it.
    Returns (batch items/sec of each call, final loss)."""
    import jax

    *state, loss = job.block(*job.state, *job.batch)
    jax.block_until_ready(loss)  # warmup/compile
    items = job.batch[0].shape[0] * num_batches_per_iter
    rates = []
    for _ in range(num_iters):
        t0 = time.perf_counter()
        *state, loss = job.block(*state, *job.batch)
        jax.block_until_ready(loss)
        rates.append(items / (time.perf_counter() - t0))
    return rates, float(loss)


GPT_LEARNING_RATE = 1e-4


def gpt_optimizer():
    """The optimizer of the GPT job, before ``hvt.DistributedOptimizer``."""
    import optax

    return optax.adamw(GPT_LEARNING_RATE)


def gpt_job(devices, per_chip_batch, num_batches_per_iter, dtype_name,
            seq_len=1024, use_flash=False, chunked_ce=False,
            n_kv_heads=None, unroll=1):
    """The GPT train step on a dp mesh over ``devices``: 12 layers, 768
    wide, 12 heads, vocabulary 32768, ``hvt.DistributedOptimizer`` over
    AdamW. ``state`` is ``(params, opt_state)``, ``batch`` is
    ``(tokens,)`` made from a fixed seed, ``loss_fn(params, tokens)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvt
    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.parallel.mesh import make_parallel_mesh

    n = len(devices)
    mesh = make_parallel_mesh(devices=devices, dp=n)
    dtype = jnp.float32 if dtype_name == "fp32" else jnp.bfloat16
    cfg = GPTConfig(vocab_size=32768, n_layers=12, d_model=768, n_heads=12,
                    n_kv_heads=n_kv_heads, d_ff=3072, max_seq_len=seq_len,
                    dtype=dtype, use_flash=use_flash)
    model = GPT(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (per_chip_batch * n, seq_len)))
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp")))
    # jitted: one compile instead of one per initializer op
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, seq_len), jnp.int32))["params"]
    repl = NamedSharding(mesh, P())
    params = jax.device_put(params, repl)
    tx = hvt.DistributedOptimizer(gpt_optimizer(),
                                  axis_name=None)  # pjit: XLA reduces
    opt_state = jax.device_put(tx.init(params), repl)

    def loss_fn(params, tokens):
        targets = jnp.roll(tokens, -1, axis=-1)
        if chunked_ce:
            # fuse the vocab projection into a sequence-chunked CE: the
            # [B, S, V] logits tensor is never materialized (losses.py)
            from horovod_tpu.ops.losses import softmax_cross_entropy_fused

            hidden = model.apply({"params": params}, tokens,
                                 return_hidden=True)
            return softmax_cross_entropy_fused(
                hidden[:, :-1], params["embedding"], targets[:, :-1])
        logits = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], targets[:, :-1]).mean()

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    block = _multi_step_block(train_step, 2, num_batches_per_iter, unroll)
    return TrainJob(block, (params, opt_state), (tokens,), loss_fn, cfg)


def measure_gpt(devices, per_chip_batch, num_iters, num_batches_per_iter,
                dtype_name, seq_len=1024, use_flash=False,
                chunked_ce=False, n_kv_heads=None, unroll=1):
    """GPT train-step throughput on a dp mesh (tokens/sec/chip) — the
    flagship-model counterpart of the ResNet measurement. FLOPs/token by
    the standard training estimate 6N + 12·L·d_model·seq (dense matmuls
    fwd+bwd plus attention score/value matmuls).

    Returns (per_chip_tok_sec, tok_sec_mean, tok_sec_std,
    flops_per_token, None, final_loss)."""
    import jax
    import numpy as np

    job = gpt_job(devices, per_chip_batch, num_batches_per_iter,
                  dtype_name, seq_len, use_flash=use_flash,
                  chunked_ce=chunked_ce, n_kv_heads=n_kv_heads,
                  unroll=unroll)
    n = len(devices)
    n_params = sum(x.size for x in jax.tree.leaves(job.state[0]))
    flops_per_token = (6 * n_params
                       + 12 * job.cfg.n_layers * job.cfg.d_model * seq_len)
    seq_secs, loss = _time_block(job, num_iters, num_batches_per_iter)
    tok_secs = [r * seq_len for r in seq_secs]
    tok_mean = float(np.mean(tok_secs))
    return (tok_mean / n, tok_mean, float(np.std(tok_secs)),
            flops_per_token, None, loss)


def resnet_job(model_name, devices, per_chip_batch, num_batches_per_iter,
               dtype_name, image_size=224, norm_impl="tpu",
               conv0_s2d=False, unroll=1):
    """The image-model train step on a dp mesh over ``devices``:
    ``hvt.DistributedOptimizer`` over SGD-momentum, as the reference
    benchmark. ``state`` is ``(params, batch_stats, opt_state)``,
    ``batch`` is ``(images, labels)`` made from a fixed seed, and
    ``loss_fn(params, batch_stats, images, labels)`` returns
    ``(loss, new_batch_stats)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvt
    from horovod_tpu.models import (InceptionV3, ResNet50, ResNet101,
                                    ResNet152, VGG16)
    from horovod_tpu.parallel.mesh import make_parallel_mesh

    n = len(devices)
    mesh = make_parallel_mesh(devices=devices, dp=n)
    dtype = jnp.float32 if dtype_name == "fp32" else jnp.bfloat16
    model_cls = {"resnet50": ResNet50, "resnet101": ResNet101,
                 "resnet152": ResNet152, "vgg16": VGG16,
                 "inception_v3": InceptionV3}[model_name]
    extra = ({"conv0_space_to_depth": True}
             if conv0_s2d and model_name.startswith("resnet") else {})
    model = model_cls(num_classes=1000, dtype=dtype, norm_impl=norm_impl,
                      **extra)

    global_batch = per_chip_batch * n
    rng = np.random.RandomState(0)
    images = jnp.asarray(
        rng.randn(global_batch, image_size, image_size, 3), dtype)
    labels = jnp.asarray(rng.randint(0, 1000, (global_batch,)))
    data_sharding = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())
    images = jax.device_put(images, data_sharding)
    labels = jax.device_put(labels, data_sharding)

    # jitted: one compile instead of one per initializer op
    variables = jax.jit(functools.partial(model.init, train=True))(
        jax.random.PRNGKey(0),
        jnp.zeros((1, image_size, image_size, 3), dtype))
    params, batch_stats = variables["params"], variables["batch_stats"]
    params = jax.device_put(params, repl)
    batch_stats = jax.device_put(batch_stats, repl)

    # reference benchmark uses SGD momentum 0.9 via hvd.DistributedOptimizer
    tx = hvt.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  axis_name=None)  # pjit: XLA reduces
    opt_state = jax.device_put(tx.init(params), repl)

    def loss_fn(params, batch_stats, images, labels):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, mutated["batch_stats"]

    def train_step(params, batch_stats, opt_state, images, labels):
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, new_bs, opt_state), loss

    block = _multi_step_block(train_step, 3, num_batches_per_iter, unroll)
    return TrainJob(block, (params, batch_stats, opt_state),
                    (images, labels), loss_fn)


def measure(model_name, devices, per_chip_batch, num_iters,
            num_batches_per_iter, dtype_name, image_size=224,
            norm_impl="tpu", conv0_s2d=False, unroll=1):
    """Train-step throughput on a dp mesh over ``devices``.

    Returns (per_chip_img_sec, img_sec_mean, img_sec_std, flops_per_img,
    xla_flops_per_img, final_loss)."""
    import numpy as np

    job = resnet_job(model_name, devices, per_chip_batch,
                     num_batches_per_iter, dtype_name, image_size,
                     norm_impl=norm_impl, conv0_s2d=conv0_s2d,
                     unroll=unroll)
    compiled = job.block.lower(*job.state, *job.batch).compile()
    # MFU convention: theoretical model FLOPs (literature value, scaled by
    # resolution), not compiler accounting. XLA's cost analysis counts the
    # fori_loop body ONCE (verified) and uses its own conv accounting
    # (~1.9x the algorithmic count), so it is reported separately as a
    # cross-check, never fed into mfu.
    flops_per_img = (FLOPS_PER_IMG[model_name]
                     * (image_size / NATIVE_IMG_SIZE[model_name]) ** 2)
    # the analysis is of the partitioned module: one chip's share
    total_flops = _compiled_flops(compiled)
    xla_flops_per_img = (total_flops / per_chip_batch
                         if total_flops is not None else None)

    img_secs, loss = _time_block(job._replace(block=compiled), num_iters,
                                 num_batches_per_iter)
    n = len(devices)
    img_sec_mean = float(np.mean(img_secs))
    return (img_sec_mean / n, img_sec_mean, float(np.std(img_secs)),
            flops_per_img, xla_flops_per_img, loss)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "resnet152",
                            "vgg16", "inception_v3", "gpt"])
    p.add_argument("--n-kv-heads", type=int, default=None,
                   help="gpt: grouped-query attention K/V head count "
                        "(default: n_heads=12, i.e. standard MHA; must "
                        "divide 12)")
    p.add_argument("--seq-len", type=int, default=1024,
                   help="sequence length for --model gpt")
    p.add_argument("--batch-size", type=int, default=None,
                   help="per-chip batch size. Defaults per model: 256 for "
                        "resnet, 64 for vgg16 and 128 for inception_v3 "
                        "(HBM fit at 224/299), 8 for gpt (8x1024 "
                        "tokens/chip/step)")
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--num-batches-per-iter", type=int, default=32,
                   help="training steps compiled into ONE program per "
                        "timed iter; bigger amortizes the host dispatch "
                        "and the inter-step parameter carry across "
                        "steps, at more runtime per iter")
    p.add_argument("--fp32", action="store_true",
                   help="use float32 instead of bfloat16")
    p.add_argument("--image-size", type=int, default=None,
                   help="square input resolution (default: the model's "
                        "native size — 224, or 299 for inception_v3; "
                        "smaller for CPU harness validation)")
    p.add_argument("--no-scaling", action="store_true",
                   help="skip the 1→N chip scaling sweep")
    p.add_argument("--flash", action="store_true",
                   help="gpt: pallas fused attention instead of the "
                        "einsum-softmax path")
    p.add_argument("--chunked-ce", action="store_true",
                   help="gpt: sequence-chunked fused cross-entropy — the "
                        "[B,S,V] logits tensor is never materialized "
                        "(ops/losses.py); frees HBM for larger batches")
    p.add_argument("--conv0-s2d", action="store_true",
                   help="resnet: numerically-identical space-to-depth "
                        "stem (224x224x3 7x7/2 conv -> 112x112x12 4x4/1; "
                        "the 3-channel stem starves the MXU contraction "
                        "lanes — classic public-MLPerf TPU fix)")
    p.add_argument("--unroll", type=int, default=1,
                   help="unroll factor for the steps-per-iter fori_loop: "
                        ">1 removes the while-loop barrier between steps "
                        "so XLA can overlap step i's optimizer/BN-stats "
                        "tail with step i+1's matmuls (compile time "
                        "grows with the factor)")
    p.add_argument("--bn-impl", default="tpu", choices=["tpu", "flax"],
                   help="resnet batch norm: 'tpu' = bf16-traffic "
                        "fp32-accumulated TpuBatchNorm (default), 'flax' "
                        "= stock nn.BatchNorm (fp32 statistics AND "
                        "normalization passes) for A/B comparison")
    p.add_argument("--force-cpu", nargs="?", const=2, default=None,
                   type=int, metavar="N",
                   help="run on an N-device virtual CPU mesh (default 2; "
                        "harness validation, and with N=8 the 1→2→4→8 "
                        "scaling-efficiency sweep exercises the metric of "
                        "record's full shape. CPU-mesh numbers are "
                        "RELATIVE-SHAPE-ONLY: virtual devices share one "
                        "host's cores, so per-chip efficiency conflates "
                        "collective overhead with core contention). "
                        "Without it a run that finds only the CPU fails")
    args = p.parse_args()

    if args.force_cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.force_cpu}")

    import jax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    cache_dir = enable_compile_cache()

    import horovod_tpu as hvt

    hvt.init()
    devices = jax.devices()
    n = len(devices)
    platform = devices[0].platform
    if platform == "cpu" and not args.force_cpu:
        # JAX falls back to the CPU with only a warning when it finds no
        # accelerator; a CPU timing must never pass for a device metric
        sys.exit(f"bench.py found platform {platform!r}, no accelerator; "
                 f"pass --force-cpu for harness validation")
    peak_tflops = peak_bf16_tflops(devices[0])
    print(f"# platform={platform} device_kind={devices[0].device_kind} "
          f"devices={n} compile_cache={cache_dir}", file=sys.stderr)
    dtype_name = "fp32" if args.fp32 else "bf16"

    gpt = args.model == "gpt"
    unit_item = "tok" if gpt else "img"

    def run_measure(devs, iters, bs):
        if gpt:
            return measure_gpt(devs, bs, iters, args.num_batches_per_iter,
                               dtype_name, args.seq_len,
                               use_flash=args.flash,
                               chunked_ce=args.chunked_ce,
                               n_kv_heads=args.n_kv_heads,
                               unroll=args.unroll)
        return measure(args.model, devs, bs, iters,
                       args.num_batches_per_iter, dtype_name,
                       args.image_size, norm_impl=args.bn_impl,
                       conv0_s2d=args.conv0_s2d, unroll=args.unroll)

    if not gpt and args.image_size is None:
        args.image_size = NATIVE_IMG_SIZE[args.model]
    bs = args.batch_size
    if bs is None:
        # per-model defaults; user values win. vgg16's early 224x64
        # activation maps are ~4x resnet's per image, inception runs at
        # 299 - both need smaller per-chip batches to fit HBM.
        bs = {"gpt": 8, "vgg16": 64, "inception_v3": 128}.get(
            args.model, 256)

    # The matmul chain brackets the measurement (before, after, and after
    # the sweep): the median is the `mfu` denominator and the spread shows
    # a machine whose speed moved during the run.
    calib_samples = [calibrate_matmul_tflops(platform)]

    (per_chip, rate_mean, rate_std, flops_per_item, xla_flops_per_img,
     loss) = run_measure(devices, args.num_iters, bs)
    print(f"# {args.model} bs={bs}/chip chips={n} "
          f"dtype={dtype_name}: "
          f"{rate_mean:.1f} +- {rate_std:.1f} {unit_item}/sec total, "
          f"{per_chip:.1f} {unit_item}/sec/chip, final loss {loss:.3f}",
          file=sys.stderr)

    calib_samples.append(calibrate_matmul_tflops(platform))

    # 1→N scaling sweep — metric of record (BASELINE.md): per-chip
    # throughput at n chips relative to 1 chip.
    sweep_n, sweep_eff = [1], [1.0]
    if not args.no_scaling and n > 1:
        sweep_n, per_chip_at = [], {}
        k = 1
        while k <= n:
            sweep_n.append(k)
            k *= 2
        if sweep_n[-1] != n:
            sweep_n.append(n)
        for k in sweep_n:
            if k == n:
                # headline measurement above already covers all chips
                per_chip_at[k] = per_chip
                continue
            pc = run_measure(devices[:k], max(2, args.num_iters // 2),
                             bs)[0]
            per_chip_at[k] = pc
            print(f"# scaling: {k} chips → {pc:.1f} {unit_item}/sec/chip",
                  file=sys.stderr)
        sweep_eff = [round(per_chip_at[k] / per_chip_at[1], 4)
                     for k in sweep_n]

    calib_samples.append(calibrate_matmul_tflops(platform))
    import numpy as np

    calib_tflops = float(np.median(calib_samples))
    calib_spread = ((max(calib_samples) - min(calib_samples))
                    / calib_tflops)
    achieved_tflops = per_chip * flops_per_item / 1e12
    # `mfu_vs_peak` is the conventional definition (achieved over the
    # chip's published peak), comparable to external efficiency tables
    # (reference docs/benchmarks.rst:13-14); `mfu` divides by the matmul
    # rate this harness measured on the same chip in the same run.
    mfu = achieved_tflops / calib_tflops
    mfu_vs_peak = (achieved_tflops / peak_tflops
                   if peak_tflops is not None else None)

    # Telemetry snapshot embedded in the record (metrics subsystem). The
    # eager allreduce of the final loss is a real data-plane dispatch
    # (multi-process: engine ring; single-process: immediate path), so
    # the record always carries a populated
    # hvt_collective_latency_seconds{op="allreduce"} series. A failure
    # here fails the run like any other phase.
    from horovod_tpu import metrics as hvt_metrics

    loss = float(np.asarray(hvt.allreduce(
        np.float64(loss), name="bench_final_loss")))
    metrics_snapshot = hvt_metrics.json_snapshot()
    print(f"# calib {calib_tflops:.1f} TFLOP/s/chip (median of "
          f"{len(calib_samples)} interleaved samples "
          f"{[round(c, 1) for c in calib_samples]}, spread "
          f"{calib_spread:.1%}), achieved {achieved_tflops:.2f} "
          f"TFLOP/s/chip ({flops_per_item / 1e9:.2f} "
          f"GFLOP/{unit_item}), MFU {mfu:.3f} vs measured matmul rate, "
          + ("n/a" if mfu_vs_peak is None else
             f"{mfu_vs_peak:.3f} vs {peak_tflops:.0f} TFLOP/s published "
             f"peak"),
          file=sys.stderr)

    print(json.dumps({
        "metric": f"{args.model}_synthetic_{unit_item}_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": f"{unit_item}/sec/chip",
        # Self-describing record: without the config echoed INSIDE the
        # metric line, numbers from different harness configurations look
        # comparable when they are not (see BASELINE.md).
        "config": {
            "harness": HARNESS_VERSION,
            "model": args.model,
            "dtype": dtype_name,
            "batch_per_chip": bs,
            "steps_per_iter": args.num_batches_per_iter,
            "chips": n,
            "platform": platform,
            "device_kind": devices[0].device_kind,
            "unroll": args.unroll,
            **({"seq_len": args.seq_len, "flash": bool(args.flash),
                "chunked_ce": bool(args.chunked_ce),
                "n_kv_heads": args.n_kv_heads} if gpt else
               {"image_size": args.image_size, "bn_impl": args.bn_impl,
                "conv0_s2d": bool(args.conv0_s2d)}),
        },
        # GPT has no reference-published absolute number; the ResNet
        # baseline stays the reference's 103.55 img/s/device
        "vs_baseline": (round(per_chip / BASELINE_IMG_SEC_PER_DEVICE, 3)
                        if not gpt else None),
        "mfu": round(mfu, 4),
        "mfu_vs_peak": (round(mfu_vs_peak, 4)
                        if mfu_vs_peak is not None else None),
        "peak_tflops": peak_tflops,
        "calib_tflops": round(calib_tflops, 2),
        "calib_spread": round(calib_spread, 3),
        "achieved_tflops": round(achieved_tflops, 3),
        f"flops_per_{unit_item}": round(flops_per_item / 1e9, 3),
        "xla_flops_per_img": (round(xla_flops_per_img / 1e9, 3)
                              if xla_flops_per_img is not None else None),
        # Registry snapshot (horovod_tpu.metrics): engine counters
        # (hvt_engine_cycles_total, hvt_cache_hits_total, ...) + dispatch
        # histograms ride inside the record — perf data keeps its
        # telemetry even when the live /metrics endpoint is unreachable
        "engine_metrics": metrics_snapshot,
        "scaling": {"n": sweep_n, "efficiency": sweep_eff,
                    # the sweep path itself is the metric of record
                    # (BASELINE.md, reference docs/benchmarks.rst:13);
                    # on a virtual CPU mesh the ratios conflate
                    # collective overhead with host-core contention
                    **({"caveat": "virtual CPU mesh: relative shape "
                                  "only, devices share one host's cores"}
                       if platform == "cpu" else {})},
    }))


# ---------------------------------------------------------------------------
# wire-codec convergence A/B (`bench.py --codec-ab`)
# ---------------------------------------------------------------------------
#
# Small REAL-training probe for the quantized wire codecs: 2 ranks run
# SGD on a least-squares problem whose gradient buffer carries a large
# constant "loss-scale" slot in its first 256-elem block — the fused-
# buffer shape real jobs put on the wire (tensor fusion mixes tensors
# of wildly different magnitudes into shared quantization blocks). That
# slot pins the block's absmax at every quantization stage (per-rank
# send, per-hop partial sums, the owner's roundtrip), so the true
# gradient components sharing its block sit permanently below the int8
# threshold: without error feedback they are zeroed EVERY step and
# their weights never train; the residual carry recovers them. The
# second block has no such slot and is the in-test control. Three
# configs share the identical deterministic problem: fp32 (codec off),
# int8+EF, int8−EF. The committed acceptance
# (benchmarks/r09_codec_sweep.json --check): int8+EF's final loss
# within noise of fp32, int8−EF measurably biased.


def _codec_ab_worker():
    import numpy as np

    import horovod_tpu as hvt

    hvt.init()
    r = hvt.rank()
    steps = int(os.environ.get("HVT_BENCH_AB_STEPS", "150"))
    d = 512                                  # 2 quantization blocks
    w_true = np.full(d, 0.15, np.float32)    # below the pinned threshold
    rng = np.random.RandomState(1000 + r)
    y = (w_true + rng.randn(d).astype(np.float32) * 0.01)  # rank's data
    w = np.zeros(d, np.float32)
    lr = 0.1
    aux = 100.0  # fused telemetry slot: pins block 0's absmax
    for _ in range(steps):
        g_local = (w - y).astype(np.float32)
        buf = np.concatenate(([np.float32(aux)], g_local))
        out = np.asarray(hvt.allreduce(buf, op=hvt.Average, name="grad"))
        g = out[1:]
        w = (w - lr * g).astype(np.float32)
    local_loss = 0.5 * float(np.mean((w - y) ** 2))
    losses = np.asarray(hvt.allgather(
        np.array([local_loss], np.float64), name="ab_loss"))
    if r == 0:
        print("HVT_AB_RESULT " + json.dumps(
            {"final_loss": float(losses.mean()),
             "pinned_block_coord": float(w[0]),
             "control_block_coord": float(w[400]),
             "steps": steps}), flush=True)
    hvt.shutdown()


def codec_ab_main(argv):
    """Drive the three-config A/B; prints one JSON line and optionally
    writes it (`--out`). CPU-only, ~seconds per config."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))

    def argval(flag, dflt):
        return argv[argv.index(flag) + 1] if flag in argv else dflt

    steps = argval("--steps", "150")
    out_path = argval("--out", "")
    configs = {
        "fp32": {},
        "int8_ef": {"HVT_WIRE_COMPRESSION": "int8",
                    "HVT_ERROR_FEEDBACK": "1"},
        "int8_noef": {"HVT_WIRE_COMPRESSION": "int8",
                      "HVT_ERROR_FEEDBACK": "0"},
    }
    record = {"harness": "codec_ab r1", "steps": int(steps),
              "configs": {}}
    for name, extra in configs.items():
        env = dict(os.environ)
        # the fp32 reference must actually be fp32: an ambient
        # HVT_WIRE_COMPRESSION / HVT_ERROR_FEEDBACK in the caller's
        # shell would leak into the baseline arm and collapse the A/B
        # deltas toward zero
        env.pop("HVT_WIRE_COMPRESSION", None)
        env.pop("HVT_ERROR_FEEDBACK", None)
        env.update({"HVT_BENCH_CODEC_AB": "1",
                    "HVT_BENCH_AB_STEPS": steps,
                    "HVT_SHM_ALLREDUCE": "0",  # the wire is under test
                    "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                    "PYTHONPATH": repo + os.pathsep
                    + env.get("PYTHONPATH", "") if env.get("PYTHONPATH")
                    else repo})
        env.update(extra)
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
             "2", sys.executable, os.path.abspath(__file__)],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"codec-ab config {name} failed:\n"
                               f"{proc.stdout}\n{proc.stderr}")
        for line in proc.stdout.splitlines():
            if "HVT_AB_RESULT" in line:
                record["configs"][name] = json.loads(
                    line.split("HVT_AB_RESULT ", 1)[1])
                break
        else:
            raise RuntimeError(f"no result line for {name}:\n"
                               f"{proc.stdout}")
        print(f"codec-ab {name}: "
              f"{record['configs'][name]['final_loss']:.6f}", flush=True)
    base = record["configs"]["fp32"]["final_loss"]
    record["delta_int8_ef"] = record["configs"]["int8_ef"][
        "final_loss"] - base
    record["delta_int8_noef"] = record["configs"]["int8_noef"][
        "final_loss"] - base
    print(json.dumps(record))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    return record


if __name__ == "__main__":
    if os.environ.get("HVT_BENCH_CODEC_AB"):
        _codec_ab_worker()
    elif "--codec-ab" in sys.argv:
        codec_ab_main(sys.argv)
    else:
        main()
