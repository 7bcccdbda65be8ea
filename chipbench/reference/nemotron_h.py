"""Plain reference of the hybrid decoder the ``nemotron_h`` family runs:
forward pass, loss and gradients in float32 ``jax.numpy``, no kernel, no
sort, no grouped product, no chunking of the recurrence, no flax. It reads
the package's parameter tree as data and shares no code with
``horovod_tpu.models``; ``jax.grad`` of it is the reference gradient.

The equations are those of Hugging Face's ``modeling_nemotron_h`` (the
configuration file lists the departures). Every layer is one mixer behind
one RMSNorm with a residual, ``x += mixer(rmsnorm(x))``; which mixer, the
parameter tree says (a block holds ``ssm``, ``attn`` or ``moe``):

    ssm (Mamba-2): [z | x | B | C | dt] = u W_in; xBC = silu(conv(xBC) + b),
      a causal depthwise convolution over the sequence; delta =
      softplus(dt + dt_bias), a = -exp(A_log) a head; a state h [P, N] a
      head, **position by position**:
        h_t = exp(delta_t a) h_{t-1} + delta_t x_t B_t^T
        y_t = h_t C_t + D_skip x_t
      (a head reads the B and C of its group); y = rmsnorm_by_group(y *
      silu(z)) * w over each group's channels; out = y W_out
    attn: q, k, v = h Wq, h Wk, h Wv, query head i reading key-value head
      i // (heads / kv heads); causal softmax of q k^T / sqrt(head_dim),
      **no positional term**; Wo
    moe: s = sigmoid(h Wr) over all E experts; a token's experts are the
      k largest of s + bias; its weights are s at those (not s + bias),
      divided by their sum + 1e-20 (``norm_topk_prob``) and multiplied by
      ``routed_scaling_factor``; l = h W_1; routed = sum over its experts
      e *that this share holds* of w_e down_e(relu(up_e(l))^2); out =
      routed W_2 + shared_down(relu(shared_up(h))^2)
    logits = rmsnorm(x) lm_head^T (untied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions

**A chip's share.** The tree is a share's: the Mamba-2 and attention
blocks hold some heads (and the groups or key-value heads those read) and
the equations above run over what is there, so their output is that share
of the sum. The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``: the router scores and
chooses over all ``E``, and only the held experts' terms are summed. What
the other shares would add is left out, as in the program.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``norm_eps``, ``ssm_state_size``, ``mamba_head_dim``,
``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
and ``experts_held_first`` (0 where absent). ``buffers`` is the model's
collection of that name: the router's choice bias a layer.

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


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ------------------------------------------------------------------ Mamba-2

def _conv(x, weight, bias):         # x [s, c], weight [taps, c]
    taps, s = weight.shape[0], x.shape[0]
    out = jnp.zeros_like(x) + bias
    for j in range(taps):
        back = taps - 1 - j         # tap j reads position t - back
        moved = jnp.concatenate([jnp.zeros_like(x[:back]), x[:s - back]], 0)
        out = out + weight[j] * moved
    return jax.nn.silu(out)


def mamba_mixer(u, p, config):
    """One sequence ``u [s, d]`` through the Mamba-2 mixer whose
    parameters ``p`` holds; the recurrence one position after another."""
    n, hd = config["ssm_state_size"], config["mamba_head_dim"]
    heads = p["A_log"].shape[0]
    inner = heads * hd
    bc = (p["conv_kernel"].shape[1] - inner) // 2
    groups = bc // n
    z, xbc, dt = jnp.split(u @ p["in_proj"], [inner, 2 * inner + 2 * bc], -1)
    xbc = _conv(xbc, p["conv_kernel"], p["conv_bias"])
    x, b, c = jnp.split(xbc, [inner, inner + bc], -1)
    x = x.reshape(-1, heads, hd)
    to_heads = lambda t: jnp.repeat(t.reshape(-1, groups, n),
                                    heads // groups, axis=1)
    b, c = to_heads(b), to_heads(c)                     # [s, heads, n]
    delta = jax.nn.softplus(dt + p["dt_bias"])          # [s, heads]
    a = -jnp.exp(p["A_log"])

    def position(h, at):
        x_t, b_t, c_t, delta_t = at
        h = (jnp.exp(delta_t * a)[:, None, None] * h
             + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(position, jnp.zeros((heads, hd, n), jnp.float32),
                        (x, b, c, delta))
    y = (y + p["D_skip"][:, None] * x).reshape(-1, inner)
    gated = (y * jax.nn.silu(z)).reshape(-1, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + config["norm_eps"])
    return (normed.reshape(-1, inner) * p["norm_scale"]) @ p["out_proj"]


# ---------------------------------------------------------------- attention

@jax.checkpoint
def _one_head(qkv):                 # three of [s, hd]
    q, k, v = qkv
    s = q.shape[0]
    scores = (q @ k.T) / math.sqrt(q.shape[-1])
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1) @ v


def attention(h, p):                # h [s, d]
    d = h.shape[-1]
    heads, kv_heads = p["q"]["kernel"].shape[1], p["k"]["kernel"].shape[1]
    split = lambda name, n: jnp.moveaxis(
        (h @ p[name]["kernel"].reshape(d, -1)).reshape(h.shape[0], n, -1),
        1, 0)                                           # [n, s, hd]
    q, k, v = split("q", heads), split("k", kv_heads), split("v", kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=0) for t in (k, v))
    ctx = jax.lax.map(_one_head, (q, k, v))             # [heads, s, hd]
    return jnp.moveaxis(ctx, 0, 1).reshape(h.shape[0], -1) @ p["o"][
        "kernel"].reshape(-1, d)


# ------------------------------------------------------------------ experts

def route(h, router, bias, k):
    """``h [T, d]`` -> ``(scores [T, E], experts [T, k])``: the sigmoid
    scores and the ``k`` largest of ``scores + bias`` a token."""
    scores = jax.nn.sigmoid(h @ router)
    return scores, jax.lax.top_k(scores + bias, k)[1]


def experts_layer(h, p, bias, config, forced=None):
    """The expert layer on tokens ``h [T, d]``: ``(out [T, d],
    routing)``. ``forced [T, k]`` puts another program's choice of
    experts in place of this one's (indices only: the weights stay this
    reference's own scores of those experts). ``routing`` says what
    happened: ``probs [T, E]`` (the scores with the bias, what the choice
    was made from), this reference's ``own`` choice ``[T, k]`` and the one
    ``used``."""
    n_experts, k = p["router"].shape[-1], config["num_experts_per_tok"]
    first = config.get("experts_held_first", 0)
    held = p["up"].shape[0]
    scores, own = route(h, p["router"], bias, k)
    experts = own if forced is None else forced
    # [T, E]: 1.0 where the token chose the expert
    chosen = jnp.sum(experts[..., None] == jnp.arange(n_experts), axis=1,
                     dtype=jnp.float32)
    weights = chosen * scores
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    weights = weights * config["routed_scaling_factor"]
    latent = h @ p["latent_in"]

    @jax.checkpoint
    def add_expert(out, e):
        up, down, weight = e                # weight [T]: w_e or 0
        return out + weight[:, None] * (_relu2(latent @ up) @ down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(latent), (
        p["up"], p["down"], weights[:, first:first + held].T))
    shared = _relu2(h @ p["shared_up"]) @ p["shared_down"]
    return routed @ p["latent_out"] + shared, {
        "probs": scores + bias, "own": own, "used": experts}


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
        if "ssm" in p:
            out = mamba_mixer(h, p["ssm"], config)
        elif "attn" in p:
            out = attention(h, p["attn"])
        else:
            out, layer = experts_layer(
                h, p["moe"], buffers[f"block_{i}"]["moe"]["choice_bias"],
                config, None if forced is None else forced[len(routing)])
            routing.append(layer)
        x = x + out
    x = _rmsnorm(x, params["ln_f"]["scale"], eps)
    return _cross_entropy(x, params["lm_head"], tokens), routing


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
