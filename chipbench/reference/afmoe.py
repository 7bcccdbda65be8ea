"""Plain reference of the decoder the ``afmoe`` family runs (Arcee's
Trinity models): forward pass, loss and gradients in float32
``jax.numpy``, no kernel, no sort, no grouped product, no rounds, no flax.
It reads the package's parameter tree as data and shares no code with
``horovod_tpu``; ``jax.grad`` of it is the reference gradient.

The equations are those of the published module of this ``model_type``
(``transformers``' ``models/afmoe``) at the keys a configuration of it
gives; what the catalog's ``config`` has no key for is the configuration
file's ``assumed``. ``d`` the hidden size, every norm ``x rsqrt(mean x^2 +
eps) w``, no bias anywhere:

    x_0 = E[token] sqrt(d)                          (``mup_enabled``)
    a decoder layer: h = x + N2(attn(N1(x))), y = h + N4(ffn(N3(h))), four
      norms with weights of their own: two entries of the package's tree,
      each ``x += post_norm(mixer(norm(x)))``; which mixer, the tree says
      (a block holds ``attn``, ``mlp`` or ``moe``)
    attn, on u = N1(x), H query heads on H_kv key-value heads of e: q = u
      W_q, k = u W_k, v = u W_v, g = u W_g; q and k normed a head (one
      weight [e] for all query heads, one for all key heads); **in a
      windowed layer** (``layer_types[i]`` "sliding_attention") q and k
      turned by the rotary at ``rope_theta`` over the halves of the whole
      e, **in a full layer** not turned at all; score_h(t, s) = q_h(t) .
      k_{h // (H / H_kv)}(s) e^-1/2 over s <= t in a full layer and over t
      - ``sliding_window`` < s <= t in a windowed one, **one masked
      softmax over whole rows, the mask built from positions**; o =
      softmax v; out = (o sigmoid(g)) W_o
    mlp (the ``num_dense_layers`` leading layers): down(silu(gate(z)) *
      up(z))
    moe: s = sigmoid(z W_r) over all E experts; a token's experts are the k
      largest of s + b (b a buffer, no gradient; ``n_group`` 1 and
      ``topk_group`` 1, so the groups are no limit); its weights those s
      divided by their sum + 1e-20 (``route_norm``), times ``route_scale``;
      out = sum over its experts e *that this share holds* of w_e
      down_e(silu(gate_e(z)) * up_e(z)), plus one shared SwiGLU expert of
      ``num_shared_experts x moe_intermediate_size`` that every token
      passes, no gate
    logits = norm(x) lm_head^T (untied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions

**The tree's layout.** The package holds W_q and W_g as one projection
``q [d, H, 2 e]`` with a head's columns ``[query | gate]``; this file cuts
it into the two matrices of the equations and multiplies each on its own.

**A chip's share.** The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``: the router scores and
chooses over all ``E``, the weights are renormalised over all a token
chose, and only the held experts' terms are summed. What the other shares
would add is left out, as in the program. The attention, the dense MLPs,
the router, the shared expert and the norms are whole.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``rms_norm_eps``, ``rope_theta``, ``sliding_window``,
``layer_types`` (one entry a decoder layer: the ``i``-th attention block of
the tree is layer ``i``), ``mup_enabled``, ``num_experts_per_tok``,
``route_norm``, ``route_scale`` and ``experts_held_first`` (0 where
absent). The head counts and widths are the parameter tree's shapes.

No term of the loss couples two sequences, so a sequence is walked at a
time (``lax.map``) with the queries of the attention in blocks, the
experts and the head's positions each in turn under ``jax.checkpoint``: a
directive about memory that changes no value.

A TPU multiplies float32 matrices in bf16 passes unless told otherwise,
so every entry point runs under ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Positions whose logits the loss holds at once.
HEAD_BLOCK = 2048
# Queries whose scores against every key the attention holds at once:
# [heads, block, s] float32, 268 MB at 32 heads and 16,384 positions.
QUERY_BLOCK = 128


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


# ---------------------------------------------------------------- attention

def rotary_halves(x, theta):
    """``x [s, H, e]``: channel ``j`` of the first half and ``j`` of the
    second turned against each other by ``t theta^(-2j / e)``, ``t`` the
    index along the first axis."""
    s, e = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)            # [s, 1, e / 2]
    a, b = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def seen(at, s, window):
    """``[len(at), s]``: which of the ``s`` keys the queries at positions
    ``at`` see: ``key <= at``, and with a ``window`` ``at - key < window``."""
    keys = jnp.arange(s)[None, :]
    mask = keys <= at[:, None]
    if window is not None:
        mask = mask & (at[:, None] - keys < window)
    return mask


def attention(u, p, config, windowed):
    """One sequence ``u [s, d]`` (the layer's normed input) through the
    attention whose parameters ``p`` holds, windowed and turned or full
    and unturned."""
    s, eps = u.shape[0], config["rms_norm_eps"]
    e = p["k"]["kernel"].shape[-1]
    w_q, w_g = p["q"]["kernel"][..., :e], p["q"]["kernel"][..., e:]
    q = jnp.einsum("sd,dhe->she", u, w_q)                  # [s, H, e]
    g = jnp.einsum("sd,dhe->she", u, w_g)
    k = jnp.einsum("sd,dhe->she", u, p["k"]["kernel"])     # [s, H_kv, e]
    v = jnp.einsum("sd,dhe->she", u, p["v"]["kernel"])
    q = _rmsnorm(q, p["q_norm"]["scale"], eps)
    k = _rmsnorm(k, p["k_norm"]["scale"], eps)
    if windowed:
        theta = float(config["rope_theta"])
        q, k = rotary_halves(q, theta), rotary_halves(k, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    window = config["sliding_window"] if windowed else None
    block = min(QUERY_BLOCK, s)
    pad = -s % block

    @jax.checkpoint
    def queries(args):
        q, at = args                                       # [block, H, e]
        scores = jnp.einsum("qhe,khe->hqk", q, k) / math.sqrt(e)
        probs = jax.nn.softmax(
            jnp.where(seen(at, s, window)[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khe->qhe", probs, v)

    blocks = lambda t: jnp.pad(
        t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            -1, block, *t.shape[1:])
    # a padded query sits at position 0 and sees key 0: finite, dropped
    o = jax.lax.map(queries, (blocks(q), blocks(jnp.arange(s))))
    o = o.reshape(-1, *o.shape[2:])[:s]                    # [s, H, e]
    return jnp.einsum("she,hed->sd", o * jax.nn.sigmoid(g), p["o"]["kernel"])


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
    scores, own = route(h, p["router"], bias, k)
    experts = own if forced is None else forced
    chosen = jnp.sum(experts[..., None] == jnp.arange(n_experts), axis=1,
                     dtype=jnp.float32)                 # [T, E]
    weights = chosen * scores
    if config["route_norm"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    weights = weights * config["route_scale"]

    @jax.checkpoint
    def add_expert(out, e):
        gate, up, down, weight = e          # weight [T]: w_e or 0
        return out + weight[:, None] * (
            (jax.nn.silu(h @ gate) * (h @ up)) @ down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        p["gate"], p["up"], p["down"], weights[:, first:first + held].T))
    shared = (jax.nn.silu(h @ p["shared_gate"]) * (h @ p["shared_up"])
              ) @ p["shared_down"]
    return out + shared, {"probs": scores + bias, "own": own,
                          "used": experts}


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
    eps, kinds = config["rms_norm_eps"], config["layer_types"]
    x = params["embedding"][tokens]
    if config["mup_enabled"]:
        x = x * math.sqrt(x.shape[-1])
    routing, attentions = [], 0
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = _rmsnorm(x, p["norm"]["scale"], eps)
        if "attn" in p:
            out = attention(h, p["attn"], config,
                            kinds[attentions] == "sliding_attention")
            attentions += 1
        elif "mlp" in p:
            out = dense_mlp(h, p["mlp"])
        else:
            out, layer = experts_layer(
                h, p["moe"], buffers[f"block_{i}"]["moe"]["choice_bias"],
                config, None if forced is None else forced[len(routing)])
            routing.append(layer)
        x = x + _rmsnorm(out, p["post_norm"]["scale"], eps)
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


def mixer(u, p, config, windowed):
    """An attention mixer's output on its own input ``u [n, s, d]``, a
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda u, p: jax.lax.map(
            lambda one: attention(one, p, config, windowed),
            u.astype(jnp.float32)))(
                u, jax.tree.map(lambda a: a.astype(jnp.float32), p))
