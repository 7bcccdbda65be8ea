"""Plain reference of the decoder the ``mellum`` family runs (JetBrains'
Mellum 2): forward pass, loss and gradients in float32 ``jax.numpy``, no
kernel, no sort, no grouped product, no rounds, no flax. It reads the
package's parameter tree as data and shares no code with ``horovod_tpu``
(the YaRN frequencies below are written out from the equations, not
imported); ``jax.grad`` of it is the reference gradient.

The equations are what the keys of the catalog's ``config`` define
(``model_type`` ``mellum`` has no module in this sandbox's
``transformers``; the YaRN law is that library's
``_compute_yarn_parameters`` with ``truncate`` at its default); what the
``config`` has no key for is the configuration file's ``assumed``. ``d``
the hidden size, every norm ``x rsqrt(mean x^2 + eps) w``, no bias
anywhere:

    x_0 = E[token]
    a decoder layer: h = x + attn_i(N1(x)), y = h + moe(N2(h)): two
      entries of the package's tree, each ``x += mixer(norm(x))``; which
      mixer, the tree says (a block holds ``attn`` or ``moe``)
    attn_i, on u = N1(x), H query heads on H_kv key-value heads of e: q = u
      W_q, k = u W_k, v = u W_v; q and k turned by **the layer's rotary**
      over the halves of the whole e (channel j of the first half against
      channel j of the second, by t theta_j); score_h(t, s) = q_h(t) .
      k_{h // (H / H_kv)}(s) e^-1/2 over s <= t in a ``full_attention``
      layer and over t - ``sliding_window`` < s <= t in a
      ``sliding_attention`` one, **one masked softmax over whole rows, the
      mask built from positions**; out = (softmax v) W_o
    the rotary, one entry of ``rope_parameters`` a kind of layer:
      ``rope_type`` "default": theta_j = rope_theta^(-2j / e), j = 0 .. e/2
      - 1, cos and sin as they are;
      ``rope_type`` "yarn": with c(n) = e ln(original_max_position_embeddings
      / (2 pi n)) / (2 ln rope_theta), low = floor(c(beta_fast)), high =
      ceil(c(beta_slow)), both clipped to [0, e - 1], ramp_j = clip((j -
      low) / (high - low), 0, 1):
          theta_j = rope_theta^(-2j / e) ((1 - ramp_j) + ramp_j / factor)
      and cos and sin both times ``attention_factor``
    moe: p = softmax(z W_r) over all E experts; a token's experts are the k
      largest of p; its weights those p divided by their sum
      (``norm_topk_prob``); out = sum over its experts e *that this share
      holds* of w_e down_e(silu(gate_e(z)) * up_e(z)); no shared expert, no
      choice bias
    logits = norm(x) lm_head^T (untied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions

**A chip's share.** The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``: the router scores and
chooses over all ``E``, the weights are renormalised over all a token
chose, and only the held experts' terms are summed. What the other shares
would add is left out, as in the program. The attention, the router and
the norms are whole.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``rms_norm_eps``, ``rope_parameters``,
``sliding_window``, ``layer_types`` (one entry a decoder layer: the
``i``-th attention block of the tree is layer ``i``),
``num_experts_per_tok``, ``norm_topk_prob`` and ``experts_held_first`` (0
where absent). The head counts and widths are the parameter tree's shapes.

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
import numpy as np

WINDOWED, FULL = "sliding_attention", "full_attention"
# Positions whose logits the loss holds at once.
HEAD_BLOCK = 2048
# Queries whose scores against every key the attention holds at once:
# [heads, block, s] float32, 268 MB at 32 heads and 16,384 positions.
QUERY_BLOCK = 128


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


# ------------------------------------------------------------------ rotary

def yarn_range(rope, e):
    """``(low, high)`` of a ``yarn`` entry over a head of ``e`` channels."""
    c = lambda n: (e * math.log(rope["original_max_position_embeddings"]
                                / (2 * math.pi * n))
                   / (2 * math.log(rope["rope_theta"])))
    low, high = c(rope.get("beta_fast", 32)), c(rope.get("beta_slow", 1))
    if rope.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    return max(low, 0), min(high, e - 1)


def thetas(rope, e):
    """``(theta_j [e / 2] float32, the factor on cos and sin)`` of one
    ``rope_parameters`` entry."""
    j = np.arange(e // 2, dtype=np.float64)
    plain = float(rope["rope_theta"]) ** (-2.0 * j / e)
    if rope["rope_type"] == "default":
        return jnp.asarray(plain, jnp.float32), 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    low, high = yarn_range(rope, e)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    scaled = plain * ((1.0 - ramp) + ramp / rope["factor"])
    return jnp.asarray(scaled, jnp.float32), float(rope["attention_factor"])


def rotary_halves(x, rope):
    """``x [s, H, e]``: channel ``j`` of the first half and ``j`` of the
    second turned against each other by ``t theta_j``, ``t`` the index
    along the first axis, by the law of the entry ``rope``; the result
    times the entry's factor."""
    s, e = x.shape[0], x.shape[-1]
    freqs, factor = thetas(rope, e)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    a, b = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


# ---------------------------------------------------------------- attention

def seen(at, s, window):
    """``[len(at), s]``: which of the ``s`` keys the queries at positions
    ``at`` see: ``key <= at``, and with a ``window`` ``at - key < window``."""
    keys = jnp.arange(s)[None, :]
    mask = keys <= at[:, None]
    if window is not None:
        mask = mask & (at[:, None] - keys < window)
    return mask


def attention(u, p, config, kind):
    """One sequence ``u [s, d]`` (the layer's normed input) through the
    attention whose parameters ``p`` holds, of the ``kind`` the source's
    ``layer_types`` names: its own rotary, and its window or none."""
    s = u.shape[0]
    e = p["k"]["kernel"].shape[-1]
    q = jnp.einsum("sd,dhe->she", u, p["q"]["kernel"])     # [s, H, e]
    k = jnp.einsum("sd,dhe->she", u, p["k"]["kernel"])     # [s, H_kv, e]
    v = jnp.einsum("sd,dhe->she", u, p["v"]["kernel"])
    rope = config["rope_parameters"][kind]
    q, k = rotary_halves(q, rope), rotary_halves(k, rope)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    window = config["sliding_window"] if kind == WINDOWED else None
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
    return jnp.einsum("she,hed->sd", o, p["o"]["kernel"])


# ------------------------------------------------------------------ experts

def route(h, router, k):
    """``h [T, d]`` -> ``(probs [T, E], experts [T, k])``: the softmax
    over all experts and the ``k`` largest a token."""
    probs = jax.nn.softmax(h @ router, -1)
    return probs, jax.lax.top_k(probs, k)[1]


def experts_layer(h, p, config, forced=None):
    """The expert layer on tokens ``h [T, d]``: ``(out [T, d], routing)``.
    ``forced [T, k]`` puts another program's choice of experts in place of
    this one's (indices only: the weights stay this reference's own
    probabilities of those experts). ``routing``: ``probs [T, E]``, this
    reference's ``own`` choice ``[T, k]`` and the one ``used``."""
    n_experts, k = p["router"].shape[-1], config["num_experts_per_tok"]
    first = config.get("experts_held_first", 0)
    held = p["up"].shape[0]
    probs, own = route(h, p["router"], k)
    experts = own if forced is None else forced
    chosen = jnp.sum(experts[..., None] == jnp.arange(n_experts), axis=1,
                     dtype=jnp.float32)                 # [T, E]
    weights = chosen * probs
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    @jax.checkpoint
    def add_expert(out, e):
        gate, up, down, weight = e          # weight [T]: w_e or 0
        return out + weight[:, None] * (
            (jax.nn.silu(h @ gate) * (h @ up)) @ down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        p["gate"], p["up"], p["down"], weights[:, first:first + held].T))
    return out, {"probs": probs, "own": own, "used": experts}


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


def _sequence(params, tokens, config, forced):
    """One sequence ``tokens [s]``: ``(cross entropy, routing of every
    expert layer)``."""
    eps, kinds = config["rms_norm_eps"], config["layer_types"]
    x = params["embedding"][tokens]
    routing, attentions = [], 0
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = _rmsnorm(x, p["norm"]["scale"], eps)
        if "attn" in p:
            out = attention(h, p["attn"], config, kinds[attentions])
            attentions += 1
        else:
            out, layer = experts_layer(
                h, p["moe"], config,
                None if forced is None else forced[len(routing)])
            routing.append(layer)
        x = x + out
    x = _rmsnorm(x, params["ln_f"]["scale"], eps)
    return _cross_entropy(x, params["lm_head"], tokens), routing


def _loss(params, tokens, config, forced_experts):
    """``tokens [n, s]`` -> ``(mean cross entropy, routing)``; ``routing``
    one entry an expert layer, ``T = n x s`` sequence-major, as
    ``forced_experts`` (one ``[T, k]`` an expert layer) is."""
    n, s = tokens.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    forced = None if forced_experts is None else [
        f.reshape(n, s, -1) for f in forced_experts]
    one = jax.checkpoint(lambda args: _sequence(
        params, args[0], config, args[1]))
    each, routing = jax.lax.map(one, (tokens, forced))
    return jnp.mean(each), jax.tree.map(
        lambda a: a.reshape(n * s, *a.shape[2:]), routing)


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


def mixer(u, p, config, kind):
    """An attention mixer's output on its own input ``u [n, s, d]``, a
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda u, p: jax.lax.map(
            lambda one: attention(one, p, config, kind),
            u.astype(jnp.float32)))(
                u, jax.tree.map(lambda a: a.astype(jnp.float32), p))


def experts(h, p, config, forced=None):
    """An expert layer's output on its own input ``h [T, d]`` and this
    reference's routing of it (``forced``: see ``experts_layer``)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda h, p, f: experts_layer(h, p, config, f))(
            h.astype(jnp.float32),
            jax.tree.map(lambda a: a.astype(jnp.float32), p), forced)
