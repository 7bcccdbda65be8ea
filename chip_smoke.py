#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the program still starts on the
chip: the compiled train step through ``hvt.init()`` at full model width.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the data-parallel paths on four

One process owns the chip. Each phase calls the code where it lives
(``bench.py``, ``__graft_entry__.py``, the ``hvt`` API) and prints one JSON
line; the script stops at the first phase that fails, with a non-zero exit
and no result line. On success the LAST line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. Without an accelerator (``JAX_PLATFORMS=
cpu``, or no chip) the ``launcher`` phase's worker and the ``device`` phase
both refuse the platform they find. Compiles go through the persistent
cache ``bench.enable_compile_cache`` places; every phase line carries its
compile seconds and cache hits, so a second run shows the hit.
"""

import argparse
import importlib.metadata
import json
import os
import subprocess
import sys
import time
import traceback

import bench

REPO = os.path.dirname(os.path.abspath(__file__))

# bf16 keeps 8 bits of mantissa (eps = 2**-8 = 3.9e-3). Two correct
# implementations of one attention differ by a few eps in relative L2,
# and the gradients of twelve layers built on them by a few times that
# (1.3e-2 at seq 16 in the interpreter); a wrong mask, scale or block
# differs by O(1).
BF16_REL_L2 = 2e-2
BF16_MODEL_GRAD_REL_L2 = 5e-2


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


class CompileMeter:
    """Sums what JAX reports about compilation, so that each phase can
    print what it compiled and what the persistent cache served."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        # wraps the backend compile OR the cache retrieval that replaced it
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self):
        return self.seconds, self.hits, self.misses

    def since(self, before):
        return {"compile_seconds": round(self.seconds - before[0], 2),
                "cache_hits": self.hits - before[1],
                "cache_misses": self.misses - before[2]}


def run_phase(name, fn, meter=None):
    """Run one phase, print its JSON line; exit non-zero if it fails."""
    t0 = time.perf_counter()
    before = meter.read() if meter else None
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
        record.update(meter.since(before))
    record.update(result)
    print(json.dumps(record), flush=True)


def peak_hbm(device):
    """The process's peak on ``device`` as the runtime reports it: live
    arrays are counted as "in use" and a program's temporaries as
    "reserved" (libtpu 0.0.34: 4.1 GB and 8.1 GB after the dp4 phase)."""
    stats = device.memory_stats()
    return {"peak_bytes_in_use": stats["peak_bytes_in_use"],
            "peak_bytes_reserved": stats["peak_bytes_reserved"]}


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

    import horovod_tpu as hvt
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
        return out

    # Does block_until_ready wait? Time one dependent matmul chain both
    # ways, interleaved: a scalar read back to the host cannot arrive
    # before the chain has run.
    chain, x, w, flops = bench.matmul_chain(platform)
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
    tflops = flops / t_block / 1e12
    out.update(chain_block_until_ready_s=t_block, chain_readback_s=t_read,
               chain_tflops_block_until_ready=round(tflops, 1))
    check(abs(t_block - t_read) <= 0.05 * t_read,
          f"block_until_ready ({t_block:.4f} s) and a scalar readback "
          f"({t_read:.4f} s) disagree by more than 5%")
    check(tflops <= bench.peak_bf16_tflops(devices[0]),
          f"matmul chain at {tflops:.0f} TFLOP/s is above the chip's peak")
    return out


# ---------------------------------------------------------------- train jobs

def train_blocks(job, calls):
    """Call ``job.block`` ``calls`` times from ``job.state``. Returns the
    loss after each call and the final state (the input state is
    donated)."""
    import numpy as np

    state, losses = job.state, []
    for _ in range(calls):
        *state, loss = job.block(*state, *job.batch)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return losses, state


def changed_leaves(before, after, min_ndim=0):
    """How many leaves (of at least ``min_ndim`` dimensions) of two host
    pytrees differ, and how many there are."""
    import jax
    import numpy as np

    pairs = [(a, b) for a, b in zip(jax.tree.leaves(before),
                                    jax.tree.leaves(after), strict=True)
             if np.ndim(a) >= min_ndim]
    return sum(not np.array_equal(a, b) for a, b in pairs), len(pairs)


def phase_resnet50(devices, batch=256, image_size=224, steps_per_block=2,
                   calls=3):
    """The metric-of-record model through bench.py's donated multi-step
    block: ResNet-50, hvt.DistributedOptimizer over SGD-momentum."""
    import jax

    job = bench.resnet_job("resnet50", devices, batch, steps_per_block,
                           "bf16", image_size=image_size)
    before = jax.device_get(job.state[:2])       # params, batch_stats
    losses, state = train_blocks(job, calls)
    after = jax.device_get(tuple(state[:2]))
    # Every kernel moves. The BatchNorm scales inside a block may not in
    # the first steps: each block's last scale starts at zero and holds
    # back the gradient of everything before it, and a scale at 1.0
    # moves only by updates above 6e-8.
    k_changed, k_total = changed_leaves(before[0], after[0], min_ndim=2)
    p_changed, p_total = changed_leaves(before[0], after[0])
    s_changed, s_total = changed_leaves(before[1], after[1])
    check(k_changed == k_total,
          f"only {k_changed} of {k_total} kernels changed")
    check(s_changed == s_total,
          f"only {s_changed} of {s_total} batch-statistic leaves changed")
    return {"model": "resnet50", "image_size": image_size,
            "batch_per_chip": batch, "dtype": "bf16",
            "steps": steps_per_block * calls, "losses": losses,
            "kernels_changed": f"{k_changed}/{k_total}",
            "param_leaves_changed": f"{p_changed}/{p_total}",
            "batch_stat_leaves_changed": f"{s_changed}/{s_total}"}


def gpt_train(devices, seq_len, batch, use_flash, dtype="bf16",
              steps_per_block=2, calls=2):
    """A few steps of bench.py's GPT job (12 x 768, AdamW)."""
    job = bench.gpt_job(devices, batch, steps_per_block, dtype,
                        seq_len=seq_len, use_flash=use_flash)
    losses, _ = train_blocks(job, calls)
    return {"seq_len": seq_len, "batch_per_chip": batch,
            "use_flash": use_flash, "steps": steps_per_block * calls,
            "losses": losses}


def require_compiled_flash(lowered_text):
    """The flash path under test must be the Mosaic kernel, not the
    interpreter's emulation of it."""
    from horovod_tpu.ops import flash_attention as fa

    check(not fa._interpret(),
          "flash attention would run in Pallas interpret mode here")
    check("tpu_custom_call" in lowered_text,
          "no tpu_custom_call in the lowered flash program")


def gpt_flash_vs_einsum(devices, seq_len):
    """Loss and gradients of the GPT loss with use_flash=True against
    use_flash=False, same parameters and tokens, one sequence (the einsum
    side at 4096 x 2 would leave under 1 GB of a 16 GB chip free)."""
    import jax

    flash = bench.gpt_job(devices, 1, 1, "bf16", seq_len=seq_len,
                          use_flash=True)
    einsum_loss = bench.gpt_job(devices, 1, 1, "bf16", seq_len=seq_len,
                                use_flash=False).loss_fn
    # keep the parameters, drop the optimizer state: the einsum side
    # saves 12 layers of [12, seq, seq] probabilities (11 GB at 4096)
    params, (tokens,) = flash.state[0], flash.batch
    flash_loss = flash.loss_fn
    del flash
    vg_flash = jax.jit(jax.value_and_grad(flash_loss))
    require_compiled_flash(vg_flash.lower(params, tokens).as_text())
    loss_f, grads_f = vg_flash(params, tokens)
    grads_f = jax.device_get(grads_f)
    loss_e, grads_e = jax.jit(jax.value_and_grad(einsum_loss))(params,
                                                               tokens)
    loss_f, loss_e = float(loss_f), float(loss_e)
    grad_err = rel_l2(grads_f, grads_e)
    check(abs(loss_f - loss_e) <= BF16_REL_L2 * abs(loss_e),
          f"flash loss {loss_f} vs einsum loss {loss_e}")
    check(grad_err <= BF16_MODEL_GRAD_REL_L2,
          f"flash gradients differ from einsum's by {grad_err:.4f} "
          f"(relative L2), bound {BF16_MODEL_GRAD_REL_L2}")
    return {"seq_len": seq_len, "loss_flash": loss_f, "loss_einsum": loss_e,
            "grad_rel_l2": grad_err}


def flash_kernel_vs_f32(shape):
    """One forward+backward of ``flash_attention`` alone at
    (batch, seq, heads, kv_heads, head_dim) against the einsum formula in
    float32, one head at a time (at seq 8192 twelve heads of float32
    scores would not fit the chip at once)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.flash_attention import flash_attention

    b, s, h, h_kv, d = shape
    rng = np.random.RandomState(0)
    q, do = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
             for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.bfloat16)
            for _ in range(2))

    o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True),
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
    want = [np.zeros(x.shape, np.float32) for x in (q, q, k, v)]
    with jax.default_matmul_precision("highest"):
        for i in range(h):
            j = i // (h // h_kv)
            o_i, dq_i, dk_i, dv_i = jax.device_get(
                head_ref(f32(q, i), f32(k, j), f32(v, j), f32(do, i)))
            want[0][:, :, i], want[1][:, :, i] = o_i, dq_i
            want[2][:, :, j] += dk_i
            want[3][:, :, j] += dv_i
    errs = {name: rel_l2(g, w) for name, g, w in
            zip(("o", "dq", "dk", "dv"), got, want)}
    check(all(np.isfinite(list(errs.values()))) and
          max(errs.values()) <= BF16_REL_L2,
          f"flash_attention at {shape} differs from the float32 einsum "
          f"formula: {errs} (relative L2), bound {BF16_REL_L2}")
    return {"shape": list(shape), "rel_l2": errs}


def phase_gpt(devices):
    """bench.py's GPT job on the einsum path and on the compiled flash
    kernel, the two compared, and the kernel alone at the shape whose dK/dV
    tile is capped and at the benchmark's long-sequence cell's."""
    return {
        "model": "gpt 12x768 vocab 32768",
        "einsum_1024": gpt_train(devices, 1024, 8, use_flash=False),
        "flash_4096": gpt_train(devices, 4096, 2, use_flash=True),
        "flash_vs_einsum_4096": gpt_flash_vs_einsum(devices, 4096),
        "kernel_8192": flash_kernel_vs_f32((1, 8192, 12, 12, 64)),
        # the benchmark cell gpt2l-s4096's own attention call
        "kernel_gpt2l_s4096": flash_kernel_vs_f32((2, 4096, 20, 20, 64)),
    }


# --------------------------------------------------------------------- eager

def phase_eager(params):
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
    out = hvt.broadcast_parameters(params, root_rank=0)
    changed, total = changed_leaves(jax.device_get(params),
                                    jax.device_get(out))
    check(changed == 0, f"broadcast_parameters changed {changed} leaves")
    return {"allreduce": float(np.asarray(scalar)),
            "allgather_object": gathered,
            "broadcast_parameters_leaves": total,
            "csrc_build_present": os.path.exists(native._lib_path())}


# ---------------------------------------------------------------- four chips

def on_all_devices(tree, devices):
    import jax

    return all(x.sharding.device_set == set(devices)
               for x in jax.tree.leaves(tree))


def phase_dp4(devices, seq_len=1024, batch=8, steps=3):
    """The GPT step data-parallel over every chip in both spellings the
    package offers, each against the same global batch on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvt
    from horovod_tpu.parallel.mesh import WORLD_AXIS, global_mesh

    n = len(devices)
    lr_bound = 2 * bench.GPT_LEARNING_RATE * steps

    def compare(name, losses, norms, params, ref):
        loss_err = max(abs(a - b) / abs(b)
                       for a, b in zip(losses, ref["losses"]))
        norm_err = max(abs(a - b) / abs(b)
                       for a, b in zip(norms, ref["norms"])) if norms else 0
        params = jax.device_get(params)
        delta = jax.tree.map(np.subtract, params, ref["start"])
        delta_err = rel_l2(delta, ref["delta"])
        max_abs = max(float(np.max(np.abs(a - b))) for a, b in zip(
            jax.tree.leaves(params), jax.tree.leaves(ref["params"])))
        # Tolerances. Loss and gradient norm: bf16 activations, f32
        # gradients summed in another order, 1e-2 relative. Parameters:
        # AdamW moves an element by at most ~lr a step, so two correct
        # runs differ by at most 2*lr*steps wherever a near-zero gradient
        # changed sign; their whole update agrees to 5% in relative L2.
        check(loss_err <= 1e-2, f"{name}: loss differs by {loss_err:.4f}")
        check(norm_err <= 1e-2,
              f"{name}: reduced gradient norm differs by {norm_err:.4f} "
              f"(a double reduction would be {n}x)")
        check(max_abs <= lr_bound,
              f"{name}: parameters differ by {max_abs} > {lr_bound}")
        check(delta_err <= 5e-2,
              f"{name}: parameter update differs by {delta_err:.4f}")
        return {"losses": losses, "loss_rel_err": loss_err,
                "grad_norm_rel_err": norm_err,
                "param_max_abs_diff": max_abs,
                "update_rel_l2": delta_err}

    # GSPMD spelling: bench.py's job as it stands, batch sharded over dp
    job = bench.gpt_job(devices, batch, 1, "bf16", seq_len=seq_len)
    loss_fn, (tokens,) = job.loss_fn, job.batch
    start = jax.device_get(job.state[0])
    check(len({s.device for s in tokens.addressable_shards}) == n,
          "tokens are not sharded over every chip")
    check(on_all_devices(job.state, devices),
          "parameters or optimizer state are not replicated on every chip")
    compiled = job.block.lower(*job.state, *job.batch).compile()
    check("all-reduce" in compiled.as_text(),
          "no all-reduce in the compiled GSPMD step")
    job = job._replace(block=compiled)

    # the same global batch on one device: gradients of `n` micro-batches
    # averaged, then one plain AdamW update (no hvt code)
    tx_plain = bench.gpt_optimizer()
    one = devices[0]

    @jax.jit
    def ref_step(params, opt_state, tokens):
        def add_micro(total, micro_tokens):
            one = jax.value_and_grad(loss_fn)(params, micro_tokens)
            return jax.tree.map(jnp.add, total, one), None

        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
        total, _ = jax.lax.scan(
            add_micro, zero, tokens.reshape(n, -1, tokens.shape[-1]))
        loss, grads = jax.tree.map(lambda x: x / n, total)
        updates, opt_state = tx_plain.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state, loss,
                optax.global_norm(grads))

    p = jax.device_put(start, one)
    s = tx_plain.init(p)
    t_one = jax.device_put(jax.device_get(tokens), one)
    ref = {"start": start, "losses": [], "norms": []}
    for _ in range(steps):
        p, s, loss, norm = ref_step(p, s, t_one)
        ref["losses"].append(float(loss))
        ref["norms"].append(float(norm))
    ref["params"] = jax.device_get(p)
    ref["delta"] = jax.tree.map(np.subtract, ref["params"], start)
    del p, s, t_one

    losses, state = train_blocks(job, steps)
    out = {"one_device_losses": ref["losses"],
           "gspmd": compare("gspmd", losses, [], state[0], ref)}
    del state, job

    # Horovod spelling: per-chip gradients under shard_map over the global
    # mesh, reduced by DistributedOptimizer(axis_name=WORLD_AXIS). The
    # sgd(1.0) probe turns the reduced gradient into an update whose norm
    # shows a double reduction that AdamW's normalisation would hide.
    mesh = global_mesh()
    tx = hvt.DistributedOptimizer(bench.gpt_optimizer(),
                                  axis_name=WORLD_AXIS)
    probe = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS)

    def per_chip(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        reduced, _ = probe.update(grads, probe.init(params), params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, WORLD_AXIS), optax.global_norm(reduced))

    step = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P(), P(), P(WORLD_AXIS)),
        out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1))
    repl = NamedSharding(mesh, P())
    p = jax.device_put(start, repl)
    s = jax.device_put(tx.init(p), repl)
    t = jax.device_put(jax.device_get(tokens),
                       NamedSharding(mesh, P(WORLD_AXIS)))
    step = step.lower(p, s, t).compile()
    check("all-reduce" in step.as_text(),
          "no all-reduce in the compiled shard_map step")
    losses, norms = [], []
    for _ in range(steps):
        p, s, loss, norm = step(p, s, t)
        losses.append(float(loss))
        norms.append(float(norm))
    check(on_all_devices((p, s), devices),
          "shard_map step left parameters off some chip")
    out["shard_map"] = compare("shard_map", losses, norms, p, ref)
    return out


def every_chip_used(devices):
    """Per-device peak memory: replicated state (1.2 GB) and a batch
    shard's temporaries on each chip, not everything on device 0."""
    peaks = [peak_hbm(d) for d in devices]
    check(all(p["peak_bytes_in_use"] >= 1 << 30
              and p["peak_bytes_reserved"] >= 1 << 30 for p in peaks),
          f"a chip was left idle: peak bytes per device {peaks}")
    return peaks


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
    ring-attention and hierarchical sub-checks, float32) on the chips."""
    import __graft_entry__ as graft

    graft.dryrun_multichip(len(devices))
    return {"dryrun_multichip": len(devices)}


# ---------------------------------------------------------------------- main

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: only the data-parallel paths on four chips and "
                        "what they are compared with")
    p.add_argument("--worker", action="store_true",
                   help="internal: what the launcher phase starts")
    args = p.parse_args(argv)
    if args.worker:
        return worker()

    versions = {name: importlib.metadata.version(name)
                for name in ("jax", "jaxlib", "libtpu", "flax", "optax")}
    if args.chips == 1:
        # first: no backend is up in this process yet
        run_phase("launcher", phase_launcher)

    import jax

    cache_dir = bench.enable_compile_cache()
    cache_warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    print(json.dumps({"phase": "start", "versions": versions,
                      "compile_cache_dir": cache_dir,
                      "compile_cache_warm": cache_warm}), flush=True)
    meter = CompileMeter()
    run_phase("device", lambda: phase_device(args.chips), meter)
    devices = jax.devices()

    def with_peak(fn):
        return lambda: dict(fn(), **peak_hbm(devices[0]))

    if args.chips == 1:
        run_phase("resnet50", with_peak(lambda: phase_resnet50(devices)),
                  meter)
        run_phase("gpt", with_peak(lambda: phase_gpt(devices)), meter)
        run_phase("eager", lambda: phase_eager(
            bench.gpt_job(devices, 8, 1, "bf16").state[0]), meter)
    else:
        run_phase("dp4", lambda: dict(
            phase_dp4(devices), peak_hbm=every_chip_used(devices)), meter)
        run_phase("ring4", lambda: phase_ring4(devices), meter)
        run_phase("dryrun4", lambda: phase_dryrun4(devices), meter)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
