"""The gated delta rule's two Pallas kernels alone, on the chip
(``ops/gated_delta_rule.py``), at the shape of the cell ``qwen3next-s8192``
(2 x 8192, 32 value heads of 128 x 128 on 16 key heads, bf16, chunks of
128): milliseconds a forward call and a forward-and-backward call (host
clock around ``block_until_ready``, the mean of ``--calls`` calls after
one), beside the plain ``jax.numpy`` path of ``models/gdn.py``, and what the
triangular inverse costs inside the kernels: the same calls with the
inverse's float32 products at the default precision (one bf16 pass), with
no inverse at all (``T = I - N``: a wrong program, timed only) and with
other numbers of value heads a grid step (``--heads-a-step``), and a call
of ``hvt_gdn_inverse`` alone (``inverse_kernel``): as it is, with every
level a product from blocks of 1 (what it was before PR 63), with the
substitution on the diagonal blocks of 16 alone and with ``T = I - N`` (two
wrong programs, timed only: what the levels above 16 and what the system
and the write cost). Also whether Mosaic honours the float32 precision: one
``[128, 128]`` system inverted in a kernel at ``HIGHEST`` and at the default
against numpy's float64 inverse, as it is and with every level a product,
beside the plain body's ``unit_lower_inverse`` as XLA runs it.

A microbenchmark: the step's own cost is a traced run of the cell
(``python3 -m chipbench.run --workload qwen3next-s8192 --trace 1``).

    chiprun -- python benchmarks/gdn_kernels.py
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _inputs(shape):
    import jax
    import jax.numpy as jnp
    import numpy as np

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
    return (q, k, v, g, beta), do


def _ms(call, args, calls):
    import jax

    jax.block_until_ready(call(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        jax.block_until_ready(call(*args))
    return 1e3 * (time.perf_counter() - t0) / calls


def inverse_kernel(call, calls):
    """Milliseconds a call of a delta rule's inverse kernel alone
    (``call()`` makes it; both rules take their inverse from
    ``gated_delta_rule._unit_lower_inverse``): as it is and with that
    function's parts taken out, each a trace of its own."""
    import jax

    from horovod_tpu.ops import gated_delta_rule as kernels

    whole, solved = kernels._unit_lower_inverse, kernels._solved
    rounded = lambda plan: kernels._rounder(plan.state_dtype)
    parts = {
        "as_it_is": {},
        "every_level_a_product": {"_solved": lambda c: 0},
        # wrong programs, timed only
        "substitution_alone": {
            "_unit_lower_inverse": lambda n, row, col, plan:
                kernels._substituted(n, row, col, rounded(plan))},
        "without_inverse": {
            "_unit_lower_inverse": lambda n, row, col, plan:
                (row == col).astype(n.dtype) - n},
    }
    out = {}
    for name, swapped in parts.items():
        for attribute, value in swapped.items():
            setattr(kernels, attribute, value)
        jax.clear_caches()
        try:
            out[f"ms_{name}"] = _ms(call, (), calls)
        finally:
            kernels._unit_lower_inverse, kernels._solved = whole, solved
    jax.clear_caches()
    return out


def time_paths(shape, chunk, calls, heads_a_step):
    import jax

    from horovod_tpu.ops import gated_delta_rule as kernels

    args, do = _inputs(shape)
    highest, default = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT

    def both(rule):
        step = jax.jit(lambda *a: (lambda o, vjp: (o, *vjp(do)))(
            *jax.vjp(rule, *a)))
        return {"ms_forward": _ms(jax.jit(rule), args, calls),
                "ms_forward_and_backward": _ms(step, args, calls)}

    out = {"plain": both(functools.partial(kernels.gated_delta_rule_plain,
                                           chunk=chunk))}
    plan, (_, k, v, g_col, beta), _ = kernels._prepare(
        *args, chunk, jax.numpy.float32, highest)
    out["inverse_kernel"] = inverse_kernel(
        lambda: kernels._inverse_call(k, g_col, beta, plan=plan,
                                      dtype=v.dtype), calls)
    as_it_is = kernels._HEADS_A_STEP
    for heads in heads_a_step:      # value heads a grid step
        kernels._HEADS_A_STEP = heads
        jax.clear_caches()
        out[f"kernels_{heads}_heads_a_step"] = both(functools.partial(
            kernels.gated_delta_rule_kernels, chunk=chunk))
    kernels._HEADS_A_STEP = as_it_is
    jax.clear_caches()
    for name, precision in (("kernels", highest),
                            ("kernels_inverse_at_default", default)):
        out[name] = both(functools.partial(
            kernels.gated_delta_rule_kernels, chunk=chunk,
            precision=precision))
    # what is left without the inverse: T = I - N, timed and not compared
    whole = kernels._unit_lower_inverse
    kernels._unit_lower_inverse = lambda n, row, col, plan: (
        (row == col).astype(n.dtype) - n)
    jax.clear_caches()
    try:
        out["kernels_without_inverse"] = both(functools.partial(
            kernels.gated_delta_rule_kernels, chunk=chunk))
    finally:
        kernels._unit_lower_inverse = whole
        jax.clear_caches()
    return out


def inverse_precision(c=128):
    """One strictly lower system of strongly correlated keys inverted in a
    kernel, at both precisions, against float64."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from horovod_tpu.ops import _pallas
    from horovod_tpu.ops import gated_delta_rule as kernels

    rng = np.random.RandomState(1)
    keys = rng.normal(size=(c, 16)) + 3.0           # correlated: k.k near 1
    keys /= np.linalg.norm(keys, axis=1, keepdims=True)
    system = np.tril(keys @ keys.T * rng.uniform(0.5, 1, (c, 1)), -1)
    want = np.linalg.inv(np.eye(c) + system)
    err = lambda got: float(np.linalg.norm(np.asarray(got, np.float64) - want)
                            / np.linalg.norm(want))
    out = {"xla_unit_lower_inverse": err(jax.jit(kernels.unit_lower_inverse)(
        jnp.asarray(system, jnp.float32)))}
    solved = kernels._solved
    for name, levels in (("HIGHEST", ""), ("DEFAULT", ""),
                         ("HIGHEST", "_every_level_a_product")):
        plan = kernels._Plan(c, 1, 1, c, c, 1, jnp.dtype(jnp.float32),
                             getattr(jax.lax.Precision, name),
                             _pallas.interpret())

        def body(n_ref, t_ref, plan=plan):
            row, col = kernels._positions(c)
            t_ref[...] = kernels._unit_lower_inverse(n_ref[...], row, col,
                                                     plan)

        if levels:
            kernels._solved = lambda c: 0
        try:
            got = pl.pallas_call(
                body, out_shape=jax.ShapeDtypeStruct((c, c), jnp.float32),
                interpret=plan.interpret)(jnp.asarray(system, jnp.float32))
        finally:
            kernels._solved = solved
        out[f"kernel_at_{name}{levels}"] = err(got)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", default="2,8192,16,32,128,128",
                        help="batch,seq,key heads,value heads,d_k,d_v")
    parser.add_argument("--chunk", type=int, default=128)
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--heads-a-step", default="",
                        help="value heads a grid step to time besides the "
                             "kernels' own, e.g. 2,8")
    args = parser.parse_args(argv)
    import jax

    shape = tuple(int(n) for n in args.shape.split(","))
    out = {"device": jax.devices()[0].device_kind, "shape": list(shape),
           "chunk": args.chunk,
           "inverse_rel_l2_against_float64": inverse_precision(
               min(args.chunk, 128))}
    print(json.dumps(out), flush=True)
    out.update(time_paths(shape, args.chunk, args.calls, [
        int(n) for n in args.heads_a_step.split(",") if n]))
    print(json.dumps(out))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gdn_kernels.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
