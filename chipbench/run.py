"""One run of one cell of the benchmark.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process, which owns the cell's chips. It builds the cell's training
job through the package as a user's script would (``hvt.init()``,
``horovod_tpu.models``, ``hvt.DistributedOptimizer``), warms up, measures
for ``--seconds`` with one jitted, donated call per training step
dispatched from a Python loop, checks the program against the plain
reference, and prints as its LAST line one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``.

Everything that belongs to one cell, configuration, family, spelling or
metric is found by name (``BENCHMARK.json``, ``configs/``, ``workloads/``,
``families/``, ``spellings/``, ``e2e_metrics/``, ``layer_metrics/``): this
file holds none of those names. See ``chipbench/README.md``.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. ``--rehearse`` is the builder's dry run:
the family's tiny sizes on whatever platform is there, always
``"correct": false``.
"""

import time

T_START = time.perf_counter()       # set-up counts from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

if __package__ in (None, ""):       # run as a file: make the checkout importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench.setup_sources import CHECKOUT    # noqa: E402

TRACE_STEPS = 8         # whole steps traced; the window drops the first
WARMUP_STEPS = 2


def fail(message: str):
    """No result line: the driver reads a non-zero exit as a failed run."""
    print(f"chipbench: {message}", file=sys.stderr, flush=True)
    sys.exit(1)


def read_json(*parts):
    with open(os.path.join(CHECKOUT, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """The cell's entry in BENCHMARK.json, its configuration file and its
    own file, and the metrics BENCHMARK.json gives it."""
    bench = read_json("BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        fail(f"no workload {name!r} in BENCHMARK.json (it has: "
             f"{', '.join(w['name'] for w in bench['workloads'])})")
    entry = entries[0]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = read_json(config_entry["file"])
    cell = read_json("chipbench", "workloads", f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            fail(f"{name}: {key} is {cell[key]!r} in the cell's file and "
                 f"{entry[key]!r} in BENCHMARK.json")
    metrics = {group: [m for m in bench[group]
                       if name in m.get("workloads", [name])]
               for group in ("end_to_end", "per_layer")}
    return config, cell, metrics


def read_metrics(package: str, wanted, trace, run) -> dict:
    """Each metric is a module ``chipbench.<package>.<name>`` with
    ``UNIT`` and ``read(trace, run)``; one that finds nothing to read
    returns None and is left out."""
    out = {}
    for metric in wanted:
        module = importlib.import_module(
            f"chipbench.{package}.{metric['name']}")
        value = module.read(trace, run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": module.UNIT}
    return out


def run_steps(step, state, batch, *, log_every, seconds=None, steps=None,
              annotate=None):
    """Dispatch ``step`` from a Python loop until ``seconds`` have passed
    (checked at each reading) or ``steps`` are dispatched. Every
    ``log_every`` steps the host reads a loss, and it does so one step
    late: step k+1 is enqueued before step k's loss is waited for, so
    reading never drains the device. Returns the state, every step's loss
    (device scalars) and the readings ``(step index, host time)``."""
    import jax

    losses, readings, pending = [], [], None
    t0 = time.perf_counter()
    while steps is None or len(losses) < steps:
        if annotate is None:
            *state, loss = step(*state, batch)
        else:
            with annotate(len(losses)):
                *state, loss = step(*state, batch)
        losses.append(loss)
        if pending is not None:
            float(losses[pending])
            now = time.perf_counter()
            readings.append((pending, now))
            pending = None
            if seconds is not None and now - t0 >= seconds:
                break
        if len(losses) % log_every == 0:
            pending = len(losses) - 1
    jax.block_until_ready(losses[-1])
    return state, losses, readings


class Phases(dict):
    """Seconds between one mark and the next, by the phase's name."""

    def __init__(self, start):
        super().__init__()
        self._last = start

    def mark(self, name):
        now = time.perf_counter()
        self[name] = now - self._last
        self._last = now


def quartiles(values):
    import numpy as np

    return [float(np.percentile(values, q)) for q in (0, 25, 50, 75, 100)]


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], required=True)
    p.add_argument("--rehearse", action="store_true",
                   help="builder's dry run at the family's tiny sizes, on "
                        "the CPU if that is what there is; prints "
                        "\"correct\": false")
    p.add_argument("--trace-dir", default=os.path.join(
        CHECKOUT, ".chipbench_trace"),
        help="where a traced run leaves the profiler's files")
    return p.parse_args(argv)


def trace_steps(step, state, batch, log_every, directory):
    """``TRACE_STEPS`` steps under the profiler, each inside a
    ``StepTraceAnnotation``. Returns the state, the losses and the reduced
    trace (None where the trace holds no device plane, as on the CPU)."""
    import jax

    from chipbench import xplane

    shutil.rmtree(directory, ignore_errors=True)
    jax.profiler.start_trace(directory)
    try:
        state, losses, _ = run_steps(
            step, state, batch, log_every=log_every, steps=TRACE_STEPS,
            annotate=lambda i: jax.profiler.StepTraceAnnotation(
                "train", step_num=i))
    finally:
        jax.profiler.stop_trace()
    path = xplane.find(directory)
    trace = xplane.load(path) if path else None
    planes = len(trace.devices) if trace else 0
    print(f"trace: {path}, {planes} device plane(s)", flush=True)
    return state, losses, trace if planes else None


def main(argv=None):
    args = parse_args(argv)
    config, cell, wanted = load_cell(args.workload)
    chips = cell["chips"]
    if args.rehearse and "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={chips}")

    import jax
    import numpy as np

    from chipbench import compare, flops, setup_sources, xplane

    cache_dir = setup_sources.enable_compile_cache()
    meter = setup_sources.CompileMeter()

    import horovod_tpu as hvt

    # ---- set-up: the chip, the job, weights and batch from the seed, the
    # compiled step, warm-up
    hvt.init()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        fail(f"JAX's platform is {platform!r}, not 'tpu': no accelerator, "
             f"nothing to measure")
    if len(devices) < chips:
        fail(f"{len(devices)} {platform} device(s) visible, the cell asks "
             f"for {chips}")
    devices = devices[:chips]
    phases = Phases(T_START)
    mark = phases.mark
    mark("reach_the_chip")

    family = importlib.import_module(
        f"chipbench.families.{config['family']}")
    if args.rehearse:
        config = {**config, **family.REHEARSAL["config"]}
        cell = {**cell, **family.REHEARSAL["traffic"]}
    job = family.build(config, cell)
    spelled = importlib.import_module(
        f"chipbench.spellings.{cell['spelling']}").build(job, devices)

    mark("build_the_job")
    k_init, k_batch, k_check, k_verify = jax.random.split(
        jax.random.key(args.seed), 4)
    params, extra = jax.jit(
        job.init, out_shardings=spelled.state_sharding)(k_init)
    batch = jax.jit(lambda k: job.make_batch(k, chips),
                    out_shardings=spelled.batch_sharding)(k_batch)
    jax.block_until_ready((params, batch))
    mark("weights_and_batch")
    checks = list(spelled.verify_before(k_verify))
    mark("verify_before")
    opt_state = jax.jit(spelled.tx.init,
                        out_shardings=spelled.state_sharding)(params)
    lowered = jax.jit(spelled.step, donate_argnums=(0, 1, 2)).lower(
        params, extra, opt_state, batch)
    mark("trace_and_lower_the_step")
    step = lowered.compile()
    mark("compile_or_read_the_cache")
    analysis = step.memory_analysis()
    checks += spelled.verify_compiled(step)
    state = (params, extra, opt_state)
    del params, extra, opt_state, lowered
    state, warm_losses, _ = run_steps(step, state, batch, log_every=1,
                                      steps=WARMUP_STEPS)
    first_loss = float(warm_losses[0])
    mark("warm_up")
    setup_s = time.perf_counter() - T_START
    compile_seconds, programs_in_setup = meter.seconds, meter.programs

    # ---- the traced steps, then the measured window
    trace, traced_losses = None, []
    if args.trace:
        state, traced_losses, trace = trace_steps(
            step, state, batch, cell["log_every"],
            os.path.join(args.trace_dir, args.workload))
    state, losses, readings = run_steps(
        step, state, batch, log_every=cell["log_every"],
        seconds=args.seconds)
    compiled_in_window = meter.programs - programs_in_setup
    memory_stats = [d.memory_stats() or {} for d in devices]
    samples = [(b_step - a_step) * job.items_per_step_per_chip / (b_t - a_t)
               for (a_step, a_t), (b_step, b_t)
               in zip(readings, readings[1:])]
    if not samples:
        fail(f"{len(readings)} reading(s) in {args.seconds} s: no sample; "
             f"the window is too short for this cell")
    window_losses = np.asarray(jax.device_get(losses), np.float32)
    every_loss = np.concatenate(
        [np.asarray(jax.device_get(traced_losses), np.float32),
         window_losses])
    print(f"samples of {job.item}/s/chip: n={len(samples)} "
          f"min/q1/median/q3/max={quartiles(samples)}", flush=True)
    print(f"loss: first {first_loss}, after the window {every_loss[-1]}; "
          f"steps dispatched {len(losses)}", flush=True)

    # ---- correctness, after the window. The loss the measured step
    # itself returns, one step on, against the plain reference on the
    # same parameters and batch (chip 0's copy of them); then, with the
    # optimizer state freed, what the spelling and the family check.
    t_check = time.perf_counter()
    checks.append(compare.holds("every_loss_finite",
                                bool(np.all(np.isfinite(every_loss)))))
    checks.append(compare.holds("loss_fell", every_loss[-1] < first_loss,
                                f"{first_loss} -> {every_loss[-1]}"))
    on_chip_0 = jax.tree.map(lambda a: a.addressable_shards[0].data,
                             state[:2])
    want = job.reference_loss(*on_chip_0, jax.device_put(batch, devices[0]))
    state, (got,), _ = run_steps(step, state, batch, log_every=1, steps=1)
    checks.append(compare.close("step_loss_vs_reference", float(got), want,
                                job.loss_rel_bound, floor=1.0))
    params = state[0]
    del state, on_chip_0
    checks += spelled.verify_after(params)
    del params
    checks += job.check(k_check)
    for c in checks:
        print(c.line(), flush=True)
    if compiled_in_window:
        fail(f"{compiled_in_window} program(s) compiled inside the "
             f"measured window: a failed run")

    # ---- what the readers get, what a builder wants to see, the result
    run = {
        "item": job.item, "chips": chips,
        "items_per_s_chip": float(np.median(samples)),
        "items_per_step_per_chip": job.items_per_step_per_chip,
        "flops_per_item": job.flops_per_item,
        "peak": flops.peaks(devices[0].device_kind)
        if platform == "tpu" else None,
        "step_bytes": analysis.argument_size_in_bytes
        + analysis.output_size_in_bytes + analysis.temp_size_in_bytes
        - analysis.alias_size_in_bytes,
        "setup_seconds": setup_s, "compile_seconds": compile_seconds,
        "memory_stats": memory_stats, "facts": job.facts,
    }
    print(json.dumps({
        "setup_seconds": setup_s, "setup_phases": phases,
        "compile_seconds_whole_run": meter.seconds,
        "programs": meter.programs,
        "cache_hits": meter.hits, "cache_misses": meter.misses,
        "check_seconds": time.perf_counter() - t_check,
        "cache_dir": cache_dir,
        "cache_bytes": setup_sources.directory_bytes(cache_dir),
        "memory_analysis": {k: getattr(analysis, k) for k in dir(analysis)
                            if k.endswith("_in_bytes")},
        "memory_stats": memory_stats, "facts": job.facts,
        "flops_per_item": job.flops_per_item}), flush=True)

    device = {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
        # live arrays are "in use", a program's temporaries "reserved"
        "memory_peak_bytes": max(
            m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
            for m in memory_stats)}
    result = {"correct": all(c.ok for c in checks) and not args.rehearse,
              "attempted": len(losses),
              "failed": int(np.sum(~np.isfinite(window_losses)))}
    if args.trace:
        result["metrics"] = read_metrics("layer_metrics",
                                         wanted["per_layer"], trace, run)
        busy = xplane.busy_and_window_seconds(trace) if trace else None
        if busy:
            device.update(busy_s=busy[0], window_s=busy[1])
            result["breakdown"] = xplane.breakdown(trace)
    else:
        result["metrics"] = read_metrics("e2e_metrics",
                                         wanted["end_to_end"], None, run)
    result["device"] = device
    hvt.shutdown()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
