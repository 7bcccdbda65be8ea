"""Plain reference of the decoder the ``lfm2_moe`` family runs: forward
pass, loss and gradients in float32 ``jax.numpy``, no kernel, no sort, no
grouped product, no rounds, no flax. It reads the package's parameter tree
as data and shares no code with ``horovod_tpu.models``; ``jax.grad`` of it
is the reference gradient.

The equations are those of Hugging Face's ``modeling_lfm2_moe`` (the
configuration file lists the departures). A decoder layer of the source is
``h = x + op(norm(x))``, ``y = h + ffn(norm(h))``, two entries of the
package's tree, each ``x += mixer(norm(x))`` with the norm ``x rsqrt(mean
x^2 + eps) w``; which mixer, the parameter tree says (a block holds
``sconv``, ``attn``, ``mlp`` or ``moe``):

    sconv (gated short convolution): [B | C | u] = h W_in; v = B * u;
      c_t = sum_{j < taps} w_j v_{t - taps + 1 + j} a channel, zeros
      before the sequence, no bias, no activation, **position by
      position** (a window of the last ``taps`` values of v carried
      along the sequence); out = (C * c) W_out
    attn: q, k, v a head = h Wq, h Wk, h Wv; q and k the norm above over a
      head's channels, one weight vector for all heads; a full rotary
      (the halves of a head against each other, base ``rope_theta``);
      query head i reads key-value head i // (heads / kv heads); causal
      softmax of q k^T / sqrt(head_dim); out = attn Wo
    mlp: down(silu(gate(h)) * up(h))
    moe: s = sigmoid(h Wr) over all E experts; a token's experts are the
      k largest of s + b (``use_expert_bias``: b a buffer, no gradient);
      its weights those s divided by their sum + 1e-6
      (``norm_topk_prob``), times ``routed_scaling_factor``; out = sum
      over its experts e *that this share holds* of w_e
      down_e(silu(gate_e(h)) * up_e(h)); no shared expert
    logits = norm(x) embedding^T (tied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions

**A chip's share.** The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``: the router scores and
chooses over all ``E``, the weights are renormalised over all a token
chose, and only the held experts' terms are summed. What the other shares
would add is left out, as in the program. The mixers, the dense MLP, the
router and the norms are whole.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``norm_eps``, ``conv_L_cache``, ``rope_parameters``
(its ``rope_theta``), ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``use_expert_bias`` and ``experts_held_first``
(0 where absent).

No term of the loss couples two sequences, so a sequence is walked at a
time (``lax.map``) with the heads, the experts and the head's positions
each in turn under ``jax.checkpoint``: a directive about memory that
changes no value.

A TPU multiplies float32 matrices in bf16 passes unless told otherwise,
so every entry point runs under ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Positions whose logits the loss holds at once.
HEAD_BLOCK = 2048


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


# --------------------------------------------------- gated short convolution

def short_conv(h, p, config):
    """One sequence ``h [s, d]`` through the gated short convolution whose
    parameters ``p`` holds; the convolution one position after another."""
    d, taps = h.shape[-1], config["conv_L_cache"]
    b, c, u = jnp.split(h @ p["in_proj"].reshape(d, -1), 3, -1)

    def position(before, v_t):          # before [taps - 1, d]: v_{t-2}, v_{t-1}
        window = jnp.concatenate([before, v_t[None]], 0)
        return window[1:], jnp.sum(p["conv_kernel"] * window, 0)

    _, conv = jax.lax.scan(position, jnp.zeros((taps - 1, d), h.dtype), b * u)
    return (c * conv) @ p["out_proj"]


# ---------------------------------------------------------------- attention

def _rotary(x, theta):              # x [n, s, hd]
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@jax.checkpoint
def _one_head(qkv):                 # three of [s, hd]
    q, k, v = qkv
    s = q.shape[0]
    scores = (q @ k.T) / math.sqrt(q.shape[-1])
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1) @ v


def attention(h, p, config):        # h [s, d]
    d, eps = h.shape[-1], config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    heads, kv_heads = p["q"]["kernel"].shape[1], p["k"]["kernel"].shape[1]
    split = lambda name, n: jnp.moveaxis(
        (h @ p[name]["kernel"].reshape(d, -1)).reshape(h.shape[0], n, -1),
        1, 0)                                           # [n, s, hd]
    q, k, v = split("q", heads), split("k", kv_heads), split("v", kv_heads)
    q = _rotary(_rmsnorm(q, p["q_norm"]["scale"], eps), theta)
    k = _rotary(_rmsnorm(k, p["k_norm"]["scale"], eps), theta)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=0) for t in (k, v))
    ctx = jax.lax.map(_one_head, (q, k, v))
    return jnp.moveaxis(ctx, 0, 1).reshape(h.shape[0], -1) @ p["o"][
        "kernel"].reshape(-1, d)


# ---------------------------------------------------- dense MLP and experts

def dense_mlp(h, p):
    return (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def route(h, router, bias, k):
    """``h [T, d]`` -> ``(scores [T, E], experts [T, k])``: the sigmoid
    scores and the ``k`` largest of ``scores + bias`` a token."""
    scores = jax.nn.sigmoid(h @ router)
    return scores, jax.lax.top_k(scores + bias, k)[1]


def experts_layer(h, p, bias, config, forced=None):
    """The expert layer on tokens ``h [T, d]``: ``(out [T, d], routing)``.
    ``forced [T, k]`` puts another program's choice of experts in place of
    this one's (indices only: the weights stay this reference's own scores
    of those experts). ``routing``: ``probs [T, E]`` (the scores with the
    bias, what the choice was made from), this reference's ``own`` choice
    ``[T, k]`` and the one ``used``."""
    n_experts, k = p["router"].shape[-1], config["num_experts_per_tok"]
    first = config.get("experts_held_first", 0)
    held = p["up"].shape[0]
    if not config["use_expert_bias"]:
        bias = jnp.zeros_like(bias)
    scores, own = route(h, p["router"], bias, k)
    experts = own if forced is None else forced
    chosen = jnp.sum(experts[..., None] == jnp.arange(n_experts), axis=1,
                     dtype=jnp.float32)                 # [T, E]
    weights = chosen * scores
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    weights = weights * config["routed_scaling_factor"]

    @jax.checkpoint
    def add_expert(out, e):
        gate, up, down, weight = e          # weight [T]: w_e or 0
        return out + weight[:, None] * (
            (jax.nn.silu(h @ gate) * (h @ up)) @ down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        p["gate"], p["up"], p["down"], weights[:, first:first + held].T))
    return out, {"probs": scores + bias, "own": own, "used": experts}


# -------------------------------------------------------------------- model

def _cross_entropy(x, head, tokens):        # x [s, d] normed, tokens [s]
    s = x.shape[0] - 1
    block = min(HEAD_BLOCK, s)
    pad = -s % block
    x, targets = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                  for t in (x[:-1], tokens[1:]))

    @jax.checkpoint
    def positions(xt):
        x, t = xt
        logits = x @ head.T
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked

    each = jax.lax.map(positions, (x.reshape(-1, block, x.shape[-1]),
                                   targets.reshape(-1, block)))
    return jnp.sum(each.reshape(-1)[:s]) / s


def _n_layers(params) -> int:
    return sum(1 for name in params if name.startswith("block_"))


def _sequence(params, buffers, tokens, config, forced):
    """One sequence ``tokens [s]``: ``(cross entropy, routing of every
    expert layer)``."""
    eps = config["norm_eps"]
    x = params["embedding"][tokens]
    routing = []
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = _rmsnorm(x, p["norm"]["scale"], eps)
        if "sconv" in p:
            out = short_conv(h, p["sconv"], config)
        elif "attn" in p:
            out = attention(h, p["attn"], config)
        elif "mlp" in p:
            out = dense_mlp(h, p["mlp"])
        else:
            out, layer = experts_layer(
                h, p["moe"], buffers[f"block_{i}"]["moe"]["choice_bias"],
                config, None if forced is None else forced[len(routing)])
            routing.append(layer)
        x = x + out
    x = _rmsnorm(x, params["ln_f"]["scale"], eps)
    return _cross_entropy(x, params["embedding"], tokens), routing


def _loss(params, buffers, tokens, config, forced_experts):
    """``tokens [n, s]`` -> ``(mean cross entropy, routing)``; ``routing``
    one entry an expert layer, ``T = n x s`` sequence-major, as
    ``forced_experts`` (one ``[T, k]`` an expert layer) is."""
    n, s = tokens.shape
    params, buffers = jax.tree.map(lambda a: a.astype(jnp.float32),
                                   (params, buffers))
    forced = None if forced_experts is None else [
        f.reshape(n, s, -1) for f in forced_experts]
    one = jax.checkpoint(lambda args: _sequence(
        params, buffers, args[0], config, args[1]))
    each, routing = jax.lax.map(one, (tokens, forced))
    return jnp.mean(each), jax.tree.map(
        lambda a: a.reshape(n * s, *a.shape[2:]), routing)


def loss(params, buffers, tokens, config, forced_experts=None):
    """``(training loss of the batch tokens [n, s], routing)``."""
    with jax.default_matmul_precision("highest"):
        value, routing = jax.jit(
            lambda p, b, t, f: _loss(p, b, t, config, f))(
                params, buffers, tokens, forced_experts)
        return float(value), routing


def loss_and_grad(params, buffers, tokens, config, forced_experts=None):
    """``((loss, routing), float32 gradient)`` of the same."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, b, t, f: _loss(p, b, t, config, f), has_aux=True))(
                params, buffers, tokens, forced_experts)
