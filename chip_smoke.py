#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the program still starts on the
chip, for what no cell of the benchmark decides.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # what exists only across four

One process owns the chip. Each phase calls the code where it lives
(``__graft_entry__.py``, the ``hvt`` API, the kernels) and prints one JSON
line; the script stops at the first phase that fails, with a non-zero exit
and no result line. On success the LAST line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. Without an accelerator (``JAX_PLATFORMS=
cpu``, or no chip) the ``launcher`` phase's worker and the ``device`` phase
both refuse the platform they find. Compiles go through the persistent
cache ``chipbench.setup_sources`` places; every phase line carries its
compile seconds and cache hits, so a second run shows the hit.

Phases, each something no cell of the benchmark decides. One chip:
``launcher`` (``hvtrun --backend jax`` reaches the chip), ``device``
(topology; ``block_until_ready`` waits), ``flash8192`` (the kernels at 2 x
8192, four query heads on one key-value head of 128, the attention of the
cell ``nemotron3s-s8192``, against the float32 formula: only at that length
do they stream more than one sequence tile a grid step on a chip, and the
cell compares its gradients at 2048 positions), ``flash256`` (the same
kernels at a head of 256, 16 query heads on 2 key-value heads, the attention
of the cell ``qwen3next-s8192``), ``mla8192`` (the same kernels at a
query-key width of 192 on a value width of 128, 32 heads, the products over
positions of the cell ``kanana2-s8192``'s latent attention), ``gdn8192`` (the chunked gated delta rule
of ``models/gdn.py`` at that cell's shape, the Pallas kernels and the plain
``jax.numpy`` path side by side, each against the float32 recurrence and
timed, forward alone and forward and backward), ``kda8192`` (the same for
the delta rule with a decay a key channel of ``models/kda.py`` at the cell
``kimilinear-s8192``'s shape, ``ops/channel_delta_rule.py``'s kernels and
plain body, ``dg`` by channel), ``conv8192`` (the causal
depthwise convolution in front of that rule and of Mamba-2's scan, at the
two cells' widths, the Pallas kernels of ``ops/causal_conv.py`` and the
plain body side by side, forward and the three gradients), ``norms8192``
(that mixer's per-head norms on ``[b, s, H d]``, the L2 norm of q and k and
``RMSNorm(o) w silu(z)``, the Pallas kernels of ``ops/head_norm.py`` and the
plain bodies side by side against the float32 formula), ``eager`` (the
immediate path); ``--phases`` names the ones to run. Four
chips: ``device``, ``ring4`` (``parallel/sequence.py``, ``sp`` = 4),
``dryrun4`` (the GSPMD dp x sp x tp step against one device).

What PR 21's model phases decided, ``correct`` decides in every run of a
cell (``python3 -m chipbench.run``) at published size: ``resnet50`` ->
``resnet50-b256``; ``gpt`` -> ``gpt2l-s1024``, ``gpt2l-s4096`` (loss and
two-layer gradients against a float32 reference, flash backward included);
``dp4``'s ``shard_map`` half -> ``gpt2l-dp4`` (all-reduce, reduced gradient's
norm, bit-identical parameters); its GSPMD half -> ``dryrun4`` here.
"""

import argparse
import functools
import importlib.metadata
import json
import os
import subprocess
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

# bf16 keeps 8 bits of mantissa (eps = 2**-8 = 3.9e-3). Two correct
# implementations of one attention differ by a few eps in relative L2,
# and a wrong mask, scale or block by O(1).
BF16_REL_L2 = 2e-2


class PhaseFailed(Exception):
    """A check of a phase did not hold."""


def check(cond, message):
    if not cond:
        raise PhaseFailed(message)


def rel_l2(a, b):
    """||a - b|| / ||b|| over two pytrees, in float32 on the host."""
    import jax
    import numpy as np

    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum(y ** 2))
    return (num / den) ** 0.5


def with_gradients(fn, cot):
    """A jitted ``fn`` that also returns every operand's gradient for the
    output's cotangent ``cot``: ``(out, *gradients)``."""
    import jax

    return jax.jit(lambda *a: (lambda o, vjp: (o, *vjp(cot)))(
        *jax.vjp(fn, *a)))


def mean_seconds(call, *args):
    """Seconds a call of ``call(*args)`` on the host's clock around
    ``block_until_ready``: the mean of five after one."""
    import jax

    jax.block_until_ready(call(*args))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(call(*args))
    return (time.perf_counter() - t0) / 5


def run_phase(name, fn, meter=None):
    """Run one phase, print its JSON line; exit non-zero if it fails."""
    t0 = time.perf_counter()
    before = (meter.seconds, meter.hits, meter.misses) if meter else None
    try:
        result = fn()
    except Exception as e:  # the boundary: report which phase, then stop
        traceback.print_exc()
        print(json.dumps({"phase": name,
                          "failed": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        sys.exit(1)
    record = {"phase": name,
              "seconds": round(time.perf_counter() - t0, 2)}
    if meter:
        record.update(compile_seconds=round(meter.seconds - before[0], 2),
                      cache_hits=meter.hits - before[1],
                      cache_misses=meter.misses - before[2])
    record.update(result)
    print(json.dumps(record), flush=True)


# ------------------------------------------------------------------ launcher

def worker():
    """What ``hvtrun -np 1 --backend jax`` starts: ``hvt.init()``, the
    platform, one small compiled step through ``DistributedOptimizer``."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvt

    hvt.init()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke worker: hvt.init() found platform "
                 f"{device.platform!r}, not 'tpu'")
    tx = hvt.DistributedOptimizer(optax.sgd(0.1), axis_name=None)
    w = jnp.zeros((128, 128), jnp.float32)
    x = jnp.ones((8, 128), jnp.float32)

    @jax.jit
    def step(w, opt_state):
        loss, g = jax.value_and_grad(
            lambda w: jnp.mean((x @ w - 1.0) ** 2))(w)
        updates, opt_state = tx.update(g, opt_state, w)
        return optax.apply_updates(w, updates), opt_state, loss

    w, _, loss = step(w, tx.init(w))
    loss = float(loss)
    if loss != 1.0 or not bool(jnp.all(w > 0)):
        sys.exit(f"chip_smoke worker: wrong step (loss {loss})")
    print(json.dumps({"worker": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "size": hvt.size(), "loss": loss}}),
          flush=True)
    hvt.shutdown()


def phase_launcher():
    """The ``hvtrun`` form docs/running.md gives for TPU hosts. Runs before
    this process initialises a backend: the worker needs the chip, and
    gives it back when it exits."""
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "1",
           "--backend", "jax", sys.executable, os.path.abspath(__file__),
           "--worker"]
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        out, _ = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        # the launcher's workers die with it (safe_exec: PDEATHSIG)
        proc.kill()
        out, _ = proc.communicate()
        raise PhaseFailed(f"hvtrun did not finish in 300 s: {out[-800:]}")
    found = [line[line.index('{"worker"'):] for line in out.splitlines()
             if '{"worker"' in line]
    check(proc.returncode == 0 and found,
          f"hvtrun -np 1 --backend jax failed (rc {proc.returncode}): "
          f"{out[-1200:]}")
    return json.loads(found[-1])


# -------------------------------------------------------------------- device

def phase_device(n_chips):
    """``hvt.init()`` on the chip; the topology ``hvt`` reports agrees with
    JAX's device list; on one chip, ``block_until_ready`` waits for the
    device (a four-chip run holds only what exists across chips)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import horovod_tpu as hvt
    from chipbench.flops import peaks
    from horovod_tpu.parallel.mesh import global_mesh

    hvt.init()
    devices = jax.devices()
    platform = devices[0].platform
    check(platform == "tpu",
          f"hvt.init() found platform {platform!r}, not 'tpu': no "
          f"accelerator, nothing to check")
    check(len(devices) == n_chips,
          f"{len(devices)} chip(s) visible, this run is for {n_chips}")
    check(hvt.size() == len(devices) == jax.device_count(),
          f"hvt.size() {hvt.size()} != {len(devices)} devices")
    check(hvt.local_size() == jax.local_device_count(),
          f"hvt.local_size() {hvt.local_size()}")
    check(hvt.rank() == 0, f"hvt.rank() {hvt.rank()}")
    check(set(global_mesh().devices.flat) == set(devices),
          "global_mesh() does not span every chip")
    out = {"platform": platform, "kind": devices[0].device_kind,
           "count": len(devices), "size": hvt.size(),
           "local_size": hvt.local_size(), "rank": hvt.rank(),
           "hbm_bytes_limit": devices[0].memory_stats()["bytes_limit"]}
    if n_chips > 1:
        # this start's split (docs/troubleshooting.md, "a slow start")
        return {**out, "startup": hvt.startup_report()}

    # Does block_until_ready wait? Time one dependent matmul chain both
    # ways, interleaved: a scalar read back to the host cannot arrive
    # before the chain has run.
    m, k_steps = 8192, 8
    x, w = (jnp.asarray(np.random.RandomState(seed).randn(m, m),
                        jnp.bfloat16) for seed in (0, 1))
    chain = jax.jit(lambda x, w: lax.fori_loop(
        0, k_steps, lambda i, h: h @ w, x))
    read = jax.jit(lambda x, w: jnp.sum(chain(x, w).astype(jnp.float32)))
    jax.block_until_ready(chain(x, w))
    float(read(x, w))
    blocked, readback = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x, w))
        blocked.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(read(x, w))
        readback.append(time.perf_counter() - t0)
    t_block, t_read = float(np.median(blocked)), float(np.median(readback))
    tflops = k_steps * 2 * m ** 3 / t_block / 1e12
    out.update(chain_block_until_ready_s=t_block, chain_readback_s=t_read,
               chain_tflops_block_until_ready=round(tflops, 1))
    check(abs(t_block - t_read) <= 0.05 * t_read,
          f"block_until_ready ({t_block:.4f} s) and a scalar readback "
          f"({t_read:.4f} s) disagree by more than 5%")
    # the one table of the chip's peak; a device not in it raises
    peak = peaks(devices[0].device_kind)["bf16_flops_per_s"] / 1e12
    check(tflops <= peak, f"matmul chain at {tflops:.0f} TFLOP/s is above "
                          f"the chip's peak of {peak:.0f}")
    return {**out, "startup": hvt.startup_report()}


# ----------------------------------------------------------------- flash8192

def require_compiled_flash(lowered_text):
    """The flash path under test must be the Mosaic kernel, not the
    interpreter's emulation of it."""
    from horovod_tpu.ops import _pallas

    check(not _pallas.interpret(),
          "flash attention would run in Pallas interpret mode here")
    check("tpu_custom_call" in lowered_text,
          "no tpu_custom_call in the lowered flash program")


def flash_kernel_vs_f32(shape, rotated=0):
    """One forward+backward of ``flash_attention`` alone at
    (batch, seq, heads, kv_heads, head_dim) and, where the values' width
    is not the keys', that width after them, against the einsum formula in
    float32, one head at a time (at seq 8192 twelve heads of float32
    scores would not fit the chip at once). With ``rotated`` the last that
    many of the query-key columns go to the kernels as a pair of their
    own, ``q_r`` a head and the first head's ``k_r`` for every head
    (latent attention's), and the gradients come back by part: ``k_r``'s
    is the heads' sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.flash_attention import flash_attention

    b, s, h, h_kv, d, d_v = (*shape, shape[-1])[:6]
    n = d - rotated
    rng = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.normal(size=(b, s, *dims)), jnp.bfloat16)
                   for dims in ((h, d), (h_kv, d), (h_kv, d_v), (h, d_v)))
    if rotated:
        k = k.at[..., n:].set(k[:, :, :1, n:])    # one rotated key for all
        o, vjp = jax.vjp(
            lambda q_n, q_r, k_n, k_r, v: flash_attention(
                q_n, k_n, v, q_r=q_r, k_r=k_r, causal=True),
            q[..., :n], q[..., n:], k[..., :n], k[:, :, 0, n:], v)
    else:
        o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v,
                                                         causal=True),
                         q, k, v)
    got = jax.device_get((o, *vjp(do)))

    @jax.jit
    def head_ref(q, k, v, do):       # [b, s, d] each, float32
        def attend(q, k, v):
            sc = jnp.einsum("bqd,bkd->bqk", q, k) * d ** -0.5
            pos = jnp.arange(s)
            sc = jnp.where(pos[None, :] <= pos[:, None], sc, -1e30)
            return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sc, -1), v)

        o, vjp = jax.vjp(attend, q, k, v)
        return (o, *vjp(do))

    f32 = lambda x, i: x[:, :, i].astype(jnp.float32)
    want = [np.zeros(x.shape, np.float32) for x in (do, q, k, v)]
    with jax.default_matmul_precision("highest"):
        for i in range(h):
            j = i // (h // h_kv)
            o_i, dq_i, dk_i, dv_i = jax.device_get(
                head_ref(f32(q, i), f32(k, j), f32(v, j), f32(do, i)))
            want[0][:, :, i], want[1][:, :, i] = o_i, dq_i
            want[2][:, :, j] += dk_i
            want[3][:, :, j] += dv_i
    names = ("o", "dq", "dk", "dv")
    if rotated:
        o_w, dq_w, dk_w, dv_w = want
        names = ("o", "dq_n", "dq_r", "dk_n", "dk_r", "dv")
        want = (o_w, dq_w[..., :n], dq_w[..., n:], dk_w[..., :n],
                dk_w[..., n:].sum(axis=2), dv_w)
    errs = {name: rel_l2(g, w) for name, g, w in
            zip(names, got, want, strict=True)}
    check(all(np.isfinite(list(errs.values()))) and
          max(errs.values()) <= BF16_REL_L2,
          f"flash_attention at {shape} differs from the float32 einsum "
          f"formula: {errs} (relative L2), bound {BF16_REL_L2}")
    return {"shape": list(shape), "rel_l2": errs}


def compiled_flash_vs_f32(shape, rotated=0):
    """``flash_kernel_vs_f32`` once the lowered call at ``shape`` is seen to
    hold the Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention

    b, s, h, h_kv, d, d_v = (*shape, shape[-1])[:6]
    like = lambda *dims: jax.ShapeDtypeStruct((b, s, *dims), jnp.bfloat16)
    q, k, v = like(h, d - rotated), like(h_kv, d - rotated), like(h_kv, d_v)
    pair = ({"q_r": like(h, rotated), "k_r": like(rotated)} if rotated
            else {})
    require_compiled_flash(jax.jit(functools.partial(
        flash_attention, causal=True)).lower(q, k, v, **pair).as_text())
    return flash_kernel_vs_f32(shape, rotated)


def phase_flash8192(shape=(2, 8192, 4, 1, 128)):
    """The compiled kernels where each streams two tiles of 4096 positions
    a grid step, forward and backward, at the attention shape of the cell
    ``nemotron3s-s8192`` (2 x 8192, four query heads reading one key-value
    head, d = 128): that cell's own comparison of gradients runs at 2048
    positions, where a sequence is one tile."""
    return compiled_flash_vs_f32(shape)


def phase_flash256(shape=(2, 8192, 16, 2, 256)):
    """The compiled kernels at a head of 256 in groups of 8 query heads a
    key-value head, 2 x 8192: the attention shape of the cell
    ``qwen3next-s8192`` (the kernels' tiles and VMEM estimate had been
    checked at 64 and 128 alone)."""
    return compiled_flash_vs_f32(shape)


def phase_mla8192(shape=(2, 8192, 32, 32, 192, 128), rotated=64):
    """The compiled kernels at a query-key width apart from the value
    width, 2 x 8192, 32 heads of 192 on 128: the products over positions of
    the cell ``kanana2-s8192``'s latent attention (its own comparison of
    gradients runs at 2048 positions), output and gradients against the
    float32 formula, by both entries: ``whole`` with q and k 192 wide
    (what the mixer assembled until PR 49) and ``parts`` with the last 64
    columns as a rotated pair, one ``k_r`` a position for all 32 heads
    (what it hands over since)."""
    return {"whole": compiled_flash_vs_f32(shape),
            "parts": compiled_flash_vs_f32(shape, rotated)}


def rule_paths_vs_recurrence(kernels, plain, chunk, operands, do, want):
    """A chunked delta rule by its two paths side by side, the Pallas
    kernels and the plain ``jax.numpy`` body at one chunk: each one's
    output and five gradients against ``want`` (the float32 recurrence's,
    ``(o, dq, dk, dv, dg, dbeta)``), held to ``BF16_REL_L2``, and the
    milliseconds a forward alone and a forward and backward take."""
    import jax
    import numpy as np

    out = {"chunk": chunk, "kernels_compiled": jax.default_backend() != "cpu"}
    for name, body in (("kernels", kernels), ("plain", plain)):
        rule = functools.partial(body, chunk=chunk)
        step = with_gradients(rule, do)
        got = jax.block_until_ready(step(*operands))
        errs = {what: rel_l2(one, w) for what, one, w in
                zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want)}
        check(all(np.isfinite(list(errs.values())))
              and max(errs.values()) <= BF16_REL_L2,
              f"the chunked rule by its {name} path differs from the "
              f"float32 recurrence: {errs} (relative L2), bound "
              f"{BF16_REL_L2}")
        out[name] = {
            "ms_forward": 1e3 * mean_seconds(jax.jit(rule), *operands),
            "ms_forward_and_backward": 1e3 * mean_seconds(step, *operands),
            "rel_l2": errs}
    return out


# -------------------------------------------------------------------- gdn8192

def phase_gdn8192(shape=(2, 8192, 16, 32, 128, 128), chunk=128):
    """The chunked gated delta rule at the cell ``qwen3next-s8192``'s
    shape, (batch, seq, key heads, value heads, d_k, d_v) in bf16, by its
    two paths side by side: the Pallas kernels of
    ``ops/gated_delta_rule.py`` and the plain ``jax.numpy`` body beside
    them. Each: output and gradients against the float32
    recurrence taken one position after another (the benchmark's
    reference's, ``chipbench/reference/qwen3_next.py``), and the seconds a
    forward alone and a forward and backward take (host clock around
    ``block_until_ready``, the mean of five calls after one)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import qwen3_next as reference
    from horovod_tpu.ops import gated_delta_rule as rule_op
    from horovod_tpu.ops import head_norm as norm_op

    b, s, h_k, h_v, d_k, d_v = shape
    rng = np.random.RandomState(0)
    normal = lambda *dims: jnp.asarray(rng.normal(size=dims), jnp.float32)
    low = lambda t: t.astype(jnp.bfloat16)
    unit = lambda x: norm_op.l2_norm(x, eps=1e-6)
    q = low(unit(normal(b, s, h_k, d_k)) * d_k ** -0.5)
    k = low(unit(normal(b, s, h_k, d_k)))
    v, do = low(normal(b, s, h_v, d_v)), low(normal(b, s, h_v, d_v))
    g = -jnp.exp(normal(h_v)) * jax.nn.softplus(normal(b, s, h_v) + 1.0) / 16
    beta = jax.nn.sigmoid(normal(b, s, h_v))

    def recurrence(q, k, v, g, beta):       # one sequence, float32
        wide = lambda t: jnp.repeat(t, h_v // h_k, axis=1)
        return reference.delta_rule(wide(q), wide(k), v, g, beta)

    f32 = lambda t: t.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = with_gradients(lambda *a: jax.lax.map(
            lambda one: recurrence(*one), a), f32(do))(
                f32(q), f32(k), f32(v), g, beta)
    return {"shape": list(shape), **rule_paths_vs_recurrence(
        rule_op.gated_delta_rule_kernels, rule_op.gated_delta_rule_plain,
        chunk, (q, k, v, g, beta), do, want)}


# -------------------------------------------------------------------- kda8192

def phase_kda8192(shape=(2, 8192, 32, 128)):
    """The chunked delta rule with a decay a key channel at the cell
    ``kimilinear-s8192``'s shape, (batch, seq, heads, d) in bf16 with the
    initialisation's decays, by its two paths side by side: the Pallas
    kernels of ``ops/channel_delta_rule.py`` and the plain ``jax.numpy``
    body beside them, each at the module's chunk. Each: output and the
    five gradients (``dg`` by channel) against the float32 recurrence
    taken one position after another (the benchmark's reference's,
    ``chipbench/reference/kimi_linear.py``), and the milliseconds a
    forward alone and a forward and backward take (host clock around
    ``block_until_ready``, the mean of five calls after one;
    ``benchmarks/kda_rule.py`` reads the kernels' own from a trace)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.kda_rule import _inputs
    from chipbench.reference import kimi_linear as reference
    from horovod_tpu.ops import channel_delta_rule as rule_op

    (q, k, v, g, beta), do = _inputs(shape)
    f32 = lambda t: t.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = with_gradients(lambda *a: jax.lax.map(
            lambda one: reference.delta_rule(*one), a), f32(do))(
                f32(q), f32(k), f32(v), g, beta)
    chunk = rule_op.chunk_for(shape[1])
    return {"shape": list(shape), **rule_paths_vs_recurrence(
        rule_op.channel_delta_rule_kernels, rule_op.channel_delta_rule_plain,
        chunk, (q, k, v, g, beta), do, want)}


# ------------------------------------------------------------------- conv8192

def phase_conv8192(shapes=((2, 8192, 8192, False), (2, 8192, 1280, True)),
                   taps=4):
    """The causal depthwise convolution and its ``silu`` at the two cells'
    shapes, (batch, seq, channels, bias) in bf16: ``qwen3next-s8192``'s
    without a bias and ``nemotron3s-s8192``'s with one, by the Pallas
    kernels of ``ops/causal_conv.py`` and the plain ``jax.numpy`` body
    beside them, side by side. Each: output and the gradients of
    ``x``, ``weight`` and ``bias`` against the plain body on float32
    operands (the same bf16 numbers, so what differs is where each path
    rounds), and the milliseconds a forward alone and a forward and
    backward take (host clock around ``block_until_ready``, the mean of
    five calls after one; ``benchmarks/causal_conv.py`` reads the device's
    own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import causal_conv as conv_op

    out = {"taps": taps, "kernels_compiled": jax.default_backend() != "cpu"}
    for b, s, c, with_bias in shapes:
        rng = np.random.RandomState(c)
        x, g = (jnp.asarray(rng.normal(size=(b, s, c)), jnp.bfloat16)
                for _ in range(2))
        weight = jnp.asarray(rng.uniform(-0.5, 0.5, (taps, c)), jnp.float32)
        bias = (jnp.asarray(rng.normal(size=(c,)), jnp.float32)
                if with_bias else None)

        seconds = lambda call: mean_seconds(call, x, weight, bias)
        f32 = lambda t: t.astype(jnp.float32)
        want = with_gradients(conv_op.causal_conv_plain, f32(g))(
            f32(x), weight, bias)
        here = out[f"{b}x{s}x{c}"] = {"bias": with_bias}
        for name, conv in (("kernels", conv_op.causal_conv_kernels),
                           ("plain", conv_op.causal_conv_plain)):
            step = with_gradients(conv, g)
            got = jax.block_until_ready(step(x, weight, bias))
            errs = {what: rel_l2(one, w) for what, one, w in
                    zip(("o", "dx", "dweight", "dbias"), got, want)
                    if w is not None}
            check(all(np.isfinite(list(errs.values())))
                  and max(errs.values()) <= BF16_REL_L2,
                  f"the convolution at {b} x {s} x {c} by its {name} path "
                  f"differs from the plain body in float32: {errs} "
                  f"(relative L2), bound {BF16_REL_L2}")
            here[name] = {"ms_forward": 1e3 * seconds(jax.jit(conv)),
                          "ms_forward_and_backward": 1e3 * seconds(step),
                          "rel_l2": errs}
    return out


# ----------------------------------------------------------------- norms8192

def phase_norms8192(shapes=((2, 8192, 32, 128), (1, 1040, 3, 256))):
    """The Gated DeltaNet mixer's per-head norms at ``qwen3next-s8192``'s
    shape, (batch, seq, heads, a head's channels) in bf16 on ``[b, s, H
    d]``: its 32 value heads of 128, and three heads of 256 over a
    sequence the kernels' block does not divide. ``RMSNorm(o) w
    silu(z)`` and the L2 norm at q's scale, by the Pallas kernels of
    ``ops/head_norm.py`` and the plain ``jax.numpy`` bodies beside them,
    side by side. Each: output and every gradient against
    the plain body on float32 operands (the same bf16 numbers, so what
    differs is where each path rounds), and the milliseconds a forward
    alone and a forward and backward take (host clock around
    ``block_until_ready``, the mean of five calls after one;
    ``benchmarks/head_norm_kernels.py`` reads the device's own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import head_norm as norm_op

    eps = 1e-6
    out = {"kernels_compiled": jax.default_backend() != "cpu"}
    for b, s, heads, dim in shapes:
        rng = np.random.RandomState(heads * dim)
        o, z, g = (jnp.asarray(rng.normal(size=(b, s, heads * dim)),
                               jnp.bfloat16) for _ in range(3))
        w = jnp.asarray(rng.uniform(0.5, 1.5, (dim,)), jnp.float32)
        f32 = lambda t: t.astype(jnp.float32)
        here = out[f"{b}x{s}x{heads}x{dim}"] = {}
        for norm, args, names, paths in (
                ("gated", (o, z, w), ("y", "do", "dz", "dw"), {
                    "kernels": lambda *a: norm_op.gated_norm_kernels(
                        *a, eps=eps),
                    "plain": lambda *a: norm_op.gated_norm_plain(
                        *a, eps=eps)}),
                ("l2", (o,), ("y", "dx"), {
                    "kernels": lambda x: norm_op.l2_norm_kernels(
                        x, dim, eps=eps, scale=dim ** -0.5),
                    "plain": lambda x: norm_op.l2_norm_plain(
                        x, dim, eps=eps, scale=dim ** -0.5)})):
            want = with_gradients(paths["plain"], f32(g))(
                *(f32(a) if a.ndim == 3 else a for a in args))
            for name, fn in paths.items():
                step = with_gradients(fn, g)
                errs = {what: rel_l2(one, w_) for what, one, w_ in
                        zip(names, jax.block_until_ready(step(*args)), want)}
                check(all(np.isfinite(list(errs.values())))
                      and max(errs.values()) <= BF16_REL_L2,
                      f"the {norm} norm at {b} x {s} x {heads} x {dim} by "
                      f"its {name} path differs from the plain body in "
                      f"float32: {errs} (relative L2), bound {BF16_REL_L2}")
                here[f"{norm}_{name}"] = {
                    "ms_forward": 1e3 * mean_seconds(jax.jit(fn), *args),
                    "ms_forward_and_backward": 1e3 * mean_seconds(step,
                                                                  *args),
                    "rel_l2": errs}
    return out


# --------------------------------------------------------------------- eager

def phase_eager():
    """The single-process host path a training script uses between steps
    (engine/api.py's immediate path: no C++ core is built or loaded)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvt
    from horovod_tpu.engine import native

    check(not native.engine_running(), "the C++ engine is running")
    scalar = hvt.allreduce(np.float32(3.5), name="smoke_scalar")
    check(float(np.asarray(scalar)) == 3.5, f"allreduce scalar {scalar}")
    on_device = jnp.arange(8, dtype=jnp.float32)
    reduced = hvt.allreduce(on_device, name="smoke_device")
    check(isinstance(reduced, jax.Array)
          and np.array_equal(np.asarray(reduced), np.arange(8)),
          f"allreduce of a device array gave {reduced!r}")
    gathered = hvt.allgather_object({"rank": hvt.rank()})
    check(gathered == [{"rank": 0}], f"allgather_object {gathered}")
    params = {"w": jnp.ones((128, 128)), "b": jnp.zeros(128)}
    out = hvt.broadcast_parameters(params, root_rank=0)
    check(rel_l2(out, params) == 0, "broadcast_parameters changed a leaf")
    return {"allreduce": float(np.asarray(scalar)),
            "allgather_object": gathered,
            "broadcast_parameters_leaves": len(jax.tree.leaves(out)),
            "csrc_build_present": os.path.exists(native._lib_path())}


# ---------------------------------------------------------------- four chips

def phase_ring4(devices, shape=(1, 16384, 12, 12, 64)):
    """Ring attention with the compiled kernel over an ``sp`` mesh of every
    chip, forward and backward, against ``flash_attention`` over the whole
    sequence on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.mesh import make_parallel_mesh
    from horovod_tpu.parallel.sequence import ring_attention

    b, s, h, h_kv, d = shape
    mesh = make_parallel_mesh(devices=devices, sp=len(devices))
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    rng = np.random.RandomState(1)
    q_host = rng.normal(size=(b, s, h, d))
    k_host, v_host = (rng.normal(size=(b, s, h_kv, d)) for _ in range(2))

    def loss_of(attend):
        return lambda q, k, v: (
            attend(q, k, v).astype(jnp.float32) ** 2).mean()

    ring = jax.jit(jax.value_and_grad(loss_of(
        lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True,
                                       use_flash=True)), argnums=(0, 1, 2)))
    args = [jax.device_put(jnp.asarray(x, jnp.bfloat16), spec)
            for x in (q_host, k_host, v_host)]
    check(all(len({sh.device for sh in x.addressable_shards})
              == len(devices) for x in args),
          "q/k/v are not sharded over every chip")
    ring = ring.lower(*args).compile()
    check("collective-permute" in ring.as_text(),
          "no collective-permute in the compiled ring")
    require_compiled_flash(ring.as_text())
    val, grads = ring(*args)

    whole = jax.jit(jax.value_and_grad(loss_of(
        lambda q, k, v: flash_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2)))
    ref_val, ref_grads = whole(*(
        jax.device_put(jnp.asarray(x, jnp.bfloat16), devices[0])
        for x in (q_host, k_host, v_host)))
    val, ref_val = float(val), float(ref_val)
    grad_err = rel_l2(jax.device_get(grads), jax.device_get(ref_grads))
    check(abs(val - ref_val) <= BF16_REL_L2 * abs(ref_val),
          f"ring {val} vs one chip {ref_val}")
    check(grad_err <= BF16_REL_L2,
          f"ring gradients differ by {grad_err:.4f} (relative L2)")
    return {"shape": list(shape), "value": val, "one_chip_value": ref_val,
            "grad_rel_l2": grad_err}


def phase_dryrun4(devices):
    """__graft_entry__'s dry run (dp x sp x tp parity, expert, pipeline,
    ring-attention and hierarchical sub-checks, float32) on the chips.
    The expert one has been refused there since PR 26 (ROADMAP R1)."""
    import __graft_entry__ as graft

    graft.dryrun_multichip(len(devices))
    return {"dryrun_multichip": len(devices)}


# ---------------------------------------------------------------------- main

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: only what exists across four chips and what "
                        "it is compared with")
    p.add_argument("--worker", action="store_true",
                   help="internal: what the launcher phase starts")
    p.add_argument("--phases", default="",
                   help="comma-separated names: of the one-chip phases "
                        "other than 'device', only these (default: all)")
    args = p.parse_args(argv)
    if args.worker:
        return worker()

    versions = {name: importlib.metadata.version(name)
                for name in ("jax", "jaxlib", "libtpu", "flax", "optax")}
    only = set(filter(None, args.phases.split(",")))
    if args.chips == 1 and (not only or "launcher" in only):
        # first: no backend is up in this process yet
        run_phase("launcher", phase_launcher)

    import jax

    from chipbench.setup_sources import CompileMeter, enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    print(json.dumps({"phase": "start", "versions": versions,
                      "compile_cache_dir": cache_dir,
                      "compile_cache_warm": cache_warm}), flush=True)
    meter = CompileMeter()
    run_phase("device", lambda: phase_device(args.chips), meter)
    devices = jax.devices()
    if args.chips == 1:
        for name, phase in (("flash8192", phase_flash8192),
                            ("flash256", phase_flash256),
                            ("mla8192", phase_mla8192),
                            ("gdn8192", phase_gdn8192),
                            ("kda8192", phase_kda8192),
                            ("conv8192", phase_conv8192),
                            ("norms8192", phase_norms8192),
                            ("eager", phase_eager)):
            if not only or name in only:
                run_phase(name, phase, meter)
    else:
        run_phase("ring4", lambda: phase_ring4(devices), meter)
        run_phase("dryrun4", lambda: phase_dryrun4(devices), meter)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
