"""The delta rule with a decay a key channel alone, on the chip
(``ops/channel_delta_rule.py``), at the shape of the cell
``kimilinear-s8192`` (2 x 8192, 32 heads of 128 x 128, bf16): milliseconds
a forward call and a forward-and-backward call (host clock around
``block_until_ready``, the mean of ``--calls`` calls after one) of the plain
``jax.numpy`` body at each of ``--variants`` (``CHUNKxSUBxHEADS``: the
chunk, the sub-block inside which a pair takes its decay channel by
channel, and the heads of a sequence that pass at a time), its distance
from the float32 recurrence on one sequence's first ``--check`` positions,
and, with ``--profile VARIANT``, that variant's forward-and-backward call
split by operation from a profiler trace (the 40 that take most, with the
name stack each carries) beside its compiled text under ``chiprun_out/``.
``--scalar`` times ``ops/gated_delta_rule.py``'s plain body and kernels at
the same heads, a decay a head, for the price of the channels.
``--kernels`` times the rule's own Pallas kernels beside them
(``CHUNKxHEADS``: the chunk and the heads a grid step takes), with each
one's gradients' distance from the plain body's on the checked positions
and its operations from a trace (``hvt_kda_inverse``, ``hvt_kda_fwd``,
``hvt_kda_bwd`` a call). ``--inverse`` times a call of ``hvt_kda_inverse``
alone at the rule's own chunk and heads a step: as it is, with every level
of the triangular inverse a product (what it was before PR 63), with the
substitution on the diagonal blocks of 16 alone and with no inverse at all
(``benchmarks/gdn_kernels.py``'s ``inverse_kernel``, the function the two
rules share).

A microbenchmark: the step's own cost is a traced run of the cell
(``python3 -m chipbench.run --workload kimilinear-s8192 --trace 1``).

    chiprun -- python benchmarks/kda_rule.py --variants 32x8x8 --kernels 128x2,64x2
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out")


def _inputs(shape):
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, s, h, d = shape
    rng = np.random.RandomState(0)
    normal = lambda *dims: jnp.asarray(rng.normal(size=dims), jnp.float32)
    low = lambda t: t.astype(jnp.bfloat16)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = low(unit(normal(b, s, h, d)) * d ** -0.5)
    k = low(unit(normal(b, s, h, d)))
    v, do = low(normal(b, s, h, d)), low(normal(b, s, h, d))
    # the initialisation's decays: A in (1, 16), a step in (1e-3, 0.1)
    a = jnp.exp(jnp.asarray(rng.uniform(0.0, np.log(16.0), (h, 1)), jnp.float32))
    step = jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(0.1), (h, d)),
                               jnp.float32))
    g = -a * jax.nn.softplus(0.02 * normal(b, s, h, d)
                             + step + jnp.log(-jnp.expm1(-step)))
    beta = jax.nn.sigmoid(normal(b, s, h))
    return (q, k, v, g, beta), do


def _ms(call, args, calls):
    import jax

    jax.block_until_ready(call(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        jax.block_until_ready(call(*args))
    return 1e3 * (time.perf_counter() - t0) / calls


def _calls(rule, do):
    import jax
    import jax.numpy as jnp

    forward = jax.jit(rule)
    both = jax.jit(jax.grad(
        lambda *a: jnp.sum(rule(*a).astype(jnp.float32)
                           * do.astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))
    return forward, both


def _recurrence(q, k, v, g, beta):
    """One sequence ``[s, h, ..]`` position by position in float32: the
    benchmark's own reference of the rule."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import kimi_linear

    with jax.default_matmul_precision("highest"):
        return jax.jit(kimi_linear.delta_rule)(
            *(t.astype(jnp.float32) for t in (q, k, v, g, beta)))


def _profile(call, args, calls, tag):
    """The call's operations from a profiler trace: ms a call by
    operation, most first, with each one's name stack."""
    import jax

    from chipbench import regions, xplane

    directory = os.path.join(OUT, f".kda_rule_trace_{tag}")
    jax.block_until_ready(call(*args))
    jax.profiler.start_trace(directory)
    for _ in range(calls):
        jax.block_until_ready(call(*args))
    jax.profiler.stop_trace()
    path = xplane.find(directory)
    trace, names = xplane.load(path), regions.name_stacks(path)
    if not trace.devices:       # the CPU's trace has no device plane
        return None
    by_op = {}
    for op in trace.devices[0].ops:
        if op.label.startswith("while"):
            continue
        found = by_op.setdefault(op.name, [0.0, 0])
        found[0] += (op.end - op.start) / 1e6 / calls
        found[1] += 1
    top = sorted(by_op.items(), key=lambda kv: -kv[1][0])
    return {"ms_a_call": sum(ms for ms, _ in by_op.values()),
            "top": [[round(ms, 3), n // calls, name,
                     names.get(name, "")[-160:]] for name, (ms, n) in top[:40]]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="2,8192,32,128")
    ap.add_argument("--variants", default="64x8x8")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--check", type=int, default=2048)
    ap.add_argument("--profile", default=None, metavar="VARIANT")
    ap.add_argument("--scalar", action="store_true")
    ap.add_argument("--kernels", default="", metavar="CHUNKxHEADS,...")
    ap.add_argument("--inverse", action="store_true")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import channel_delta_rule as rule_op
    from horovod_tpu.ops import gated_delta_rule as scalar_op

    shape = tuple(int(x) for x in a.shape.split(","))
    args, do = _inputs(shape)
    want = _recurrence(*(t[0, :a.check] for t in args))
    out = {"device": jax.devices()[0].device_kind, "shape": shape}
    for variant in a.variants.split(","):
        chunk, rule_op.SUB, rule_op.HEADS_A_PASS = (
            int(x) for x in variant.split("x"))
        rule = lambda *t: rule_op.channel_delta_rule_plain(*t, chunk=chunk)
        forward, both = _calls(rule, do)
        got = forward(*(t[:1, :a.check] for t in args))[0].astype(jnp.float32)
        here = out[variant] = {
            "fwd_ms": _ms(forward, args, a.calls),
            "fwd_bwd_ms": _ms(both, args, a.calls),
            "rel_l2_vs_recurrence": float(
                jnp.linalg.norm(got - want) / jnp.linalg.norm(want))}
        if variant == a.profile:
            here["profile"] = _profile(both, args, a.calls, variant)
            with open(os.path.join(OUT, f"kda_rule_{variant}.hlo"), "w") as f:
                f.write(both.lower(*args).compile().as_text())
        print(json.dumps({variant: {k: v for k, v in here.items()
                                    if k != "profile"}}), flush=True)
    short = tuple(t[:1, :a.check] for t in args)
    # the mixer's own layout: the operands come and the results leave as
    # [b, s, H d] (on the chip [b, s, H, d] is tiled by (H, d), another
    # layout, and a 4-D operand here would be timed with its relayout)
    flat = lambda ts: tuple(t.reshape(*t.shape[:2], -1) for t in ts)
    heads = lambda t, like: t.reshape(like.shape)
    for variant in filter(None, a.kernels.split(",")):
        chunk, rule_op._HEADS_A_STEP = (int(x) for x in variant.split("x"))
        rule = lambda *t: rule_op.channel_delta_rule_kernels(*t, chunk=chunk)
        forward, both = _calls(rule, do[:1, :a.check])
        _, plain = _calls(lambda *t: rule_op.channel_delta_rule_plain(
            *t, chunk=chunk), do[:1, :a.check])
        got = forward(*short)[0].astype(jnp.float32)
        far = lambda x, y: float(
            jnp.linalg.norm(x.astype(jnp.float32) - y.astype(jnp.float32))
            / jnp.linalg.norm(y.astype(jnp.float32)))
        here = out[f"kernels_{variant}"] = {
            "rel_l2_vs_recurrence": far(got, want),
            "grads_rel_l2_vs_plain": dict(zip(
                ("dq", "dk", "dv", "dg", "dbeta"),
                map(far, both(*short), plain(*short))))}
        flat_rule = lambda *t: rule(*map(heads, t, args)).reshape(
            *do.shape[:2], -1)
        forward, both = _calls(flat_rule, flat([do])[0])
        here.update(fwd_ms=_ms(forward, flat(args), a.calls),
                    fwd_bwd_ms=_ms(both, flat(args), a.calls))
        profile = _profile(both, flat(args), a.calls, f"kernels_{variant}")
        here["by_operation"] = profile and [
            [ms, n, name] for ms, n, name, _ in profile["top"][:8]]
        print(json.dumps({f"kernels_{variant}": here}), flush=True)
    if a.inverse:
        from gdn_kernels import inverse_kernel      # beside this script

        plan, (_, k, v, g, beta), _ = rule_op._prepare(
            *args, rule_op.CHUNK, jnp.float32, jax.lax.Precision.HIGHEST)
        out["inverse_kernel"] = inverse_kernel(
            lambda: rule_op._inverse_call(k, g, beta, plan=plan,
                                          dtype=v.dtype), a.calls)
        print(json.dumps({"inverse_kernel": out["inverse_kernel"]}),
              flush=True)
    if a.scalar:
        q, k, v, g, beta = args
        one = (q, k, v, jnp.mean(g, -1), beta)
        for name, rule in (("plain", scalar_op.gated_delta_rule_plain),
                           ("kernels", scalar_op.gated_delta_rule_kernels)):
            forward, both = _calls(
                lambda *t: rule(*t, chunk=scalar_op.CHUNK), do)
            out[f"scalar_{name}"] = {"fwd_ms": _ms(forward, one, a.calls),
                                     "fwd_bwd_ms": _ms(both, one, a.calls)}
            print(json.dumps({f"scalar_{name}": out[f"scalar_{name}"]}),
                  flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "kda_rule.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
