"""Plain reference of the sparse decoder the ``olmoe`` family runs:
forward pass, loss and gradients in float32 ``jax.numpy``, no kernel, no
sort, no grouped product, no flax. It reads the package's parameter
tree as data and shares no code with
``horovod_tpu.models``; ``jax.grad`` of it is the reference gradient.

The equations are those of Hugging Face's ``modeling_olmoe`` (the
configuration file lists the departures):

    x += attn(rmsnorm(x));  x += experts(rmsnorm(x))
    attn: q, k, v = h Wq, h Wk, h Wv; q and k each pass an RMSNorm over
      their whole projected width (all heads together), are split into
      heads and rotated (first half / second half pairing, base
      ``rope_theta``); causal softmax scaled by head_dim^-1/2; Wo
    experts: p = softmax(h Wr) over the E experts; a token's experts are
      its k largest p, its weights those p as they are (not
      renormalised); out = sum over its experts of
      p_e x down_e(silu(gate_e(h)) * up_e(h))
    logits = rmsnorm(x) lm_head^T (untied)
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions
      + ``router_aux_loss_coef`` x sum over layers of E x sum_e f_e P_e
      + ``router_z_loss_coef`` x sum over layers of
        mean(logsumexp(h Wr)^2)

with f_e the share of the layer's assignments (tokens x k, over every
sequence of the batch) that chose expert e and P_e the mean of p_e over
those tokens. The expert sum is a loop over the experts with a mask: no
token is ever dropped, and an expert nobody chose adds zeros.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``num_experts_per_tok``, ``rope_theta``,
``rms_norm_eps`` and the two coefficients ``router_aux_loss_coef`` and
``router_z_loss_coef``.

The whole batch is one program, because f_e and P_e are taken over the
batch. So that it fits beside a training job's state at the published
widths, the heads, the experts and the sequences' logits are each walked
in turn (``lax.map``, ``lax.scan``) under ``jax.checkpoint``: a directive
about memory that changes no value.

A TPU multiplies float32 matrices in bf16 passes unless told otherwise,
so every entry point runs under ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):              # [n, s, h, hd]
    s, hd = x.shape[1], x.shape[3]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@jax.checkpoint
def _one_head(qkv):                 # three of [n, s, hd]
    q, k, v = qkv
    s = q.shape[1]
    scores = jnp.einsum("nqk,ntk->nqt", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("nqt,ntk->nqk", probs, v)


def _attention(h, p, config):       # h [n, s, d]
    n, s, d = h.shape
    heads = p["q"]["kernel"].shape[1]
    eps = config["rms_norm_eps"]
    wide = lambda name: h @ p[name]["kernel"].reshape(d, -1)
    q = _rmsnorm(wide("q"), p["q_norm"]["scale"], eps)
    k = _rmsnorm(wide("k"), p["k_norm"]["scale"], eps)
    q, k, v = (t.reshape(n, s, heads, -1) for t in (q, k, wide("v")))
    q, k = (_rotary(t, config["rope_theta"]) for t in (q, k))
    ctx = jax.lax.map(_one_head, tuple(
        jnp.moveaxis(t, 2, 0) for t in (q, k, v)))      # [h, n, s, hd]
    return jnp.moveaxis(ctx, 0, 2).reshape(n, s, -1) @ p["o"][
        "kernel"].reshape(-1, d)


def route(h, router, k):
    """``h [T, d]`` -> ``(probs [T, E], logits [T, E], experts [T, k])``,
    the ``k`` most probable experts of every token."""
    logits = h @ router
    probs = jax.nn.softmax(logits, -1)
    return probs, logits, jax.lax.top_k(probs, k)[1]


def experts_layer(h, p, k, forced=None):
    """The expert layer on tokens ``h [T, d]``: ``(out [T, d],
    load_balance, router_z, routing)``. ``forced [T, k]`` puts another
    program's choice of experts in place of this one's (indices only:
    the weights stay this reference's own probabilities of those
    experts). ``routing`` says what happened: ``probs [T, E]``, this
    reference's ``own`` choice ``[T, k]`` and the one ``used``."""
    n_experts = p["router"].shape[-1]
    probs, logits, own = route(h, p["router"], k)
    experts = own if forced is None else forced
    # [T, E]: 1.0 where the token chose the expert
    chosen = jnp.sum(experts[..., None] == jnp.arange(n_experts), axis=1,
                     dtype=jnp.float32)

    @jax.checkpoint
    def add_expert(out, e):
        gate, up, down, weight = e          # weight [T]: p_e or 0
        hidden = jax.nn.silu(h @ gate) * (h @ up)
        return out + weight[:, None] * (hidden @ down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        p["gate"], p["up"], p["down"], (chosen * probs).T))
    share = jnp.sum(chosen, 0) / (h.shape[0] * k)
    load_balance = n_experts * jnp.sum(share * jnp.mean(probs, 0))
    router_z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return out, load_balance, router_z, {"probs": probs, "own": own,
                                         "used": experts}


def _block(x, p, config, forced=None):      # x [n, s, d]
    eps = config["rms_norm_eps"]
    x = x + _attention(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"],
                       config)
    h = _rmsnorm(x, p["ln2"]["scale"], eps)
    out, load_balance, router_z, routing = experts_layer(
        h.reshape(-1, h.shape[-1]), p["moe"], config["num_experts_per_tok"],
        forced)
    return x + out.reshape(x.shape), load_balance, router_z, routing


def _cross_entropy(x, head, tokens):        # x [n, s, d], normed
    @jax.checkpoint
    def one_sequence(xt):
        x, t = xt
        logits = x[:-1] @ head.T
        picked = jnp.take_along_axis(logits, t[1:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.mean(jax.lax.map(one_sequence, (x, tokens)))


def _n_layers(params) -> int:
    return sum(1 for name in params if name.startswith("block_"))


def parts(params, tokens, config, forced_experts=None):
    """``tokens [n, s]`` -> ``(cross_entropy, load_balance, router_z,
    routing)``: the two auxiliary losses summed over the layers,
    unweighted; ``routing`` what ``experts_layer`` says of every layer
    (``T = n x s``, sequence-major). ``forced_experts``, one ``[T, k]``
    a layer, replaces the choices."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["embedding"][tokens]
    load_balance = router_z = 0.0
    routing = []
    for i in range(_n_layers(params)):
        x, lb, z, layer_routing = _block(
            x, params[f"block_{i}"], config,
            None if forced_experts is None else forced_experts[i])
        load_balance, router_z = load_balance + lb, router_z + z
        routing.append(layer_routing)
    x = _rmsnorm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return (_cross_entropy(x, params["lm_head"], tokens), load_balance,
            router_z, routing)


def total(config, cross_entropy, load_balance, router_z):
    return (cross_entropy + config["router_aux_loss_coef"] * load_balance
            + config["router_z_loss_coef"] * router_z)


def _loss(params, tokens, config, forced_experts):
    *losses, routing = parts(params, tokens, config, forced_experts)
    return total(config, *losses), routing


def loss(params, tokens, config, forced_experts=None):
    """``(training loss of the batch tokens [n, s], routing)``."""
    with jax.default_matmul_precision("highest"):
        value, routing = jax.jit(
            lambda p, t, f: _loss(p, t, config, f))(
                params, tokens, forced_experts)
        return float(value), routing


def loss_and_grad(params, tokens, config, forced_experts=None):
    """``((loss, routing), float32 gradient)`` of the same."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t, f: _loss(p, t, config, f), has_aux=True))(
                params, tokens, forced_experts)
