"""Mixture-of-experts layer: a top-k, dropless router over SwiGLU experts
(the OLMoE / Mixtral formulation), written so that the compiled step is
gathers and grouped matrix multiplications and nothing else.

For ``T = batch x seq`` tokens, ``E`` experts and ``k`` choices a token
the layer is four steps, each under a ``jax.named_scope`` of its name so
that a device trace can be split by them:

1. ``moe_route``: router logits in float32, softmax over the experts, the
   ``k`` largest probabilities of a token as its weights (not
   renormalised), the ``T x k`` assignments counted per expert and
   sorted by expert (stable), and the two auxiliary losses OLMoE trains
   with (arXiv:2409.02060): load balancing ``E x sum_e f_e P_e`` and the
   router z-loss ``mean(logsumexp(logits)^2)``.
2. ``moe_dispatch``: the tokens' rows gathered into expert order,
   ``[T x k, d]``. No capacity and no dropping: every assignment has its
   row, and an expert's group is as long as the router made it.
3. ``moe_experts``: ``down(silu(gate(x)) * up(x))`` as three grouped
   products over the group sizes: JAX's own Pallas grouped matrix
   multiplication (``jax.experimental.pallas.ops.tpu.megablox``, with
   its ``custom_vjp``), which at OLMoE's shape on a v5e took 2.2 to 2.4
   ms a product against 3.0 to 3.3 for ``jax.lax.ragged_dot`` (PERF.md,
   PR 26). The weight stacks ``[E, d, f]``, ``[E, d, f]``, ``[E, f, d]``
   are stored in float32 and multiplied in the layer's ``dtype``.
4. ``moe_combine``: rows gathered back into token order, weighted and
   summed over a token's ``k`` rows.

Dispatch and combine are a permutation and its inverse, so both
directions of both are gathers (``_take_rows``): the gradient program
holds no scatter-add, which a TPU serialises.

Expert parallelism: the stacks carry ``P("ep", ...)`` in
``moe_param_partition_spec`` (and in ``transformer.param_partition_spec``
when given an ``ep_axis``); how GSPMD divides the ragged products over
``ep`` is not yet measured on chips.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# The grouped-product implementation, as the engagement counter names it.
PRODUCT = "megablox_gmm"
# Its tile, rows x contraction x columns: the fastest of nine timed at
# 65,536 rows x 2048 x 1024 over 64 groups on a v5e (PERF.md, PR 26);
# a larger one does not fit the kernels' VMEM.
_GMM_TILE = (512, 1024, 1024)


def _count_trace(n_experts, top_k):
    """The engagement counter: one count a traced layer. Trace-time
    Python only."""
    try:
        from horovod_tpu import metrics

        metrics.counter(
            "hvt_moe_layers_traced_total",
            "mixture-of-experts layers traced into compiled programs "
            "(counted per trace, not per execution)",
            ("experts", "top_k", "product"),
        ).labels(experts=str(n_experts), top_k=str(top_k),
                 product=PRODUCT).inc()
    except Exception:
        pass  # telemetry must never break a trace


def _rows(x, index):
    return x.at[index].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, k):
    """Rows ``index // k`` of ``x [n, d]``, where ``index`` is a
    permutation of ``range(n x k)`` and ``inverse`` its inverse. The
    transpose of a gather is a scatter-add; of a permutation it is the
    gather by the inverse (then the sum over the ``k`` copies of a row),
    which is what the backward pass runs."""
    return _rows(x, index // k if k > 1 else index)


def _take_rows_fwd(x, index, inverse, k):
    return _take_rows(x, index, inverse, k), inverse


def _take_rows_bwd(k, inverse, g):
    g = _rows(g, inverse)
    if k > 1:
        g = g.reshape(-1, k, g.shape[-1]).sum(1)
    return g, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def moe_route(h, router, k):
    """``h [T, d]``, ``router [d, E]`` -> the ``k`` choices of every
    token. Returns ``(experts [T, k], weights [T, k] float32, order
    [T x k], inverse [T x k], group_sizes [E], aux, probs [T, E])``:
    ``order`` sorts the flattened assignments (token-major, so assignment
    ``i`` is choice ``i % k`` of token ``i // k``) by expert, stably;
    ``inverse`` is its inverse permutation; ``aux`` holds the two losses,
    unweighted; ``probs`` is the softmax the weights were taken from. All
    of it in float32 whatever the experts compute in."""
    n_tokens, n_experts = h.shape[0], router.shape[-1]
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(probs), k)
    # [T, k, E]; the weights and the counts as sums over it, so that
    # neither a gather's transpose nor a histogram puts a scatter in
    chosen = experts[..., None] == jnp.arange(n_experts)
    weights = jnp.sum(jnp.where(chosen, probs[:, None, :], 0.0), axis=-1)
    group_sizes = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    order = jnp.argsort(experts.reshape(-1), stable=True)
    inverse = jnp.argsort(order)
    share = group_sizes.astype(jnp.float32) / (n_tokens * k)
    aux = {
        "load_balance": n_experts * jnp.sum(share * probs.mean(axis=0)),
        "router_z": jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
    }
    return experts, weights, order, inverse, group_sizes, aux, probs


def moe_dispatch(h, order, inverse, k):
    """``h [T, d]`` -> its rows in expert order, ``[T x k, d]``."""
    return _take_rows(h, order, inverse, k)


def _interpret() -> bool:
    # only the CPU interprets (it has no Mosaic compiler)
    return jax.default_backend() == "cpu"


def _grouped_product(lhs, rhs, group_sizes):
    """``lhs [m, k]`` by ``rhs [g, k, n]``: rows of group ``i`` (the
    ``group_sizes[i]`` rows after those of group ``i - 1``) times
    ``rhs[i]``. The row tile has to divide ``m``."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    (m, k), n = lhs.shape, rhs.shape[-1]
    tile = (math.gcd(m, _GMM_TILE[0]), min(k, _GMM_TILE[1]),
            min(n, _GMM_TILE[2]))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tile, interpret=_interpret())


def moe_experts(rows, gate, up, down, group_sizes):
    """``down(silu(gate(x)) * up(x))`` of every row by its group's
    expert, multiplied in ``rows.dtype``."""
    gate, up, down = (w.astype(rows.dtype) for w in (gate, up, down))
    hidden = (jax.nn.silu(_grouped_product(rows, gate, group_sizes))
              * _grouped_product(rows, up, group_sizes))
    return _grouped_product(hidden, down, group_sizes)


def moe_combine(rows, weights, order, inverse):
    """Rows ``[T x k, d]`` in expert order -> ``[T, d]`` float32: each
    token's ``k`` rows times their weights, summed."""
    k = weights.shape[-1]
    rows = _take_rows(rows, inverse, order, 1)
    rows = rows.reshape(-1, k, rows.shape[-1]).astype(jnp.float32)
    return jnp.sum(rows * weights[..., None], axis=1)


class MoEMlp(nn.Module):
    """The expert layer of a block: ``n_experts`` SwiGLU experts of width
    ``d_ff``, ``experts_per_token`` of them a token, nothing dropped.
    Returns ``(out, aux)``; ``aux`` is ``{"load_balance", "router_z"}``,
    each loss unweighted (OLMoE trains with 0.01 and 0.001). For a
    caller that asks for the collection ``intermediates``, what the
    router saw and said is sown there: ``router_input [T, d]``,
    ``router_probs [T, E]`` and the chosen ``experts [T, k]``."""

    n_experts: int
    d_ff: int
    experts_per_token: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, self.n_experts))
        gate = self.param("gate", init, (self.n_experts, d, self.d_ff))
        up = self.param("up", init, (self.n_experts, d, self.d_ff))
        down = self.param("down", init, (self.n_experts, self.d_ff, d))
        _count_trace(self.n_experts, self.experts_per_token)
        h = x.reshape(-1, d)
        with jax.named_scope("moe_route"):
            (experts, weights, order, inverse, group_sizes, aux,
             probs) = moe_route(h, router, self.experts_per_token)
        for name, value in (("router_input", h), ("router_probs", probs),
                            ("experts", experts)):
            self.sow("intermediates", name, value)
        with jax.named_scope("moe_dispatch"):
            rows = moe_dispatch(h.astype(self.dtype), order, inverse,
                                self.experts_per_token)
        with jax.named_scope("moe_experts"):
            rows = moe_experts(rows, gate, up, down, group_sizes)
        with jax.named_scope("moe_combine"):
            out = moe_combine(rows, weights, order, inverse)
        return out.reshape(x.shape).astype(x.dtype), aux


def expert_leaf_spec(name: str, leaf, ep_axis, tp_axis):
    """PartitionSpec of one leaf of a ``MoEMlp``: the expert stacks
    shard their first axis over ``ep_axis`` and their ``d_ff`` axis over
    ``tp_axis``; the router replicates."""
    if leaf.ndim == 3 and name in ("gate", "up"):
        return P(ep_axis, None, tp_axis)
    if leaf.ndim == 3 and name == "down":
        return P(ep_axis, tp_axis, None)
    return P()


def moe_param_partition_spec(params, ep_axis: str = "ep",
                             tp_axis: Optional[str] = None):
    """PartitionSpecs for an MoE param tree: expert-stacked weights
    ([n_experts, ...]) shard over ``ep_axis`` (dim 0); everything else
    replicated (compose with the dense model's tp spec separately)."""

    def spec(path, leaf):
        last = str(getattr(path[-1], "key", path[-1])) if path else ""
        return expert_leaf_spec(last, leaf, ep_axis, tp_axis)

    return jax.tree_util.tree_map_with_path(spec, params)
