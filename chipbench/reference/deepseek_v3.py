"""Plain reference of the decoder the ``deepseek_v3`` family runs: forward
pass, loss and gradients in float32 ``jax.numpy``, no kernel, no sort, no
grouped product, no rounds, no flax. It reads the package's parameter tree
as data and shares no code with ``horovod_tpu.models``; ``jax.grad`` of it
is the reference gradient.

The equations are those of Hugging Face's ``modeling_deepseek_v3`` at the
keys a configuration of that ``model_type`` gives (the configuration file
lists the departures). A decoder layer of the source is ``h = x +
attn(norm(x))``, ``y = h + ffn(norm(h))``, two entries of the package's
tree, each ``x += mixer(norm(x))`` with the norm ``x rsqrt(mean x^2 + eps)
w``; which mixer, the parameter tree says (a block holds ``mla``, ``mlp``
or ``moe``):

    mla (multi-head latent attention, ``q_lora_rank`` null): q = h W_q, a
      head's n + e columns q_n | q_r; [c | k_r] = h W_kva (r + e columns);
      c <- norm(c) with a weight [r]; [k_n | v] a head = c W_kvb (n + v
      columns a head); k_r is **one** e-vector a position for every head.
      The rotary at ``rope_theta`` on q_r (every head) and on k_r, over
      the **interleaved pairs** (2j, 2j + 1) of the e channels at angle
      t theta^(-2j / e) (``rope_interleave``; the source regroups the
      pairs into halves and then rotates halves: the scores are the
      same); ``rope_scaling`` null. score_h(t, u) = (q_n,h(t) . k_n,h(u)
      + q_r,h(t) . k_r(u)) (n + e)^-1/2, causal softmax, o_h = sum_u p
      v_h(u); out = [o_1 .. o_H] W_o
    mlp: down(silu(gate(h)) * up(h))
    moe: s = sigmoid(h W_r) over all E experts; a token's experts are the
      k largest of s + b (``noaux_tc``: b a buffer, no gradient; ``n_group``
      1 and ``topk_group`` 1, so the groups are no limit); its weights
      those s divided by their sum + 1e-20 (``norm_topk_prob``), times
      ``routed_scaling_factor``; out = sum over its experts e *that this
      share holds* of w_e down_e(silu(gate_e(h)) * up_e(h)), plus the
      shared experts as one ungated SwiGLU of ``n_shared_experts x
      moe_intermediate_size`` that every token passes
    logits = norm(x) lm_head^T (untied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions

**A chip's share.** The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``: the router scores and
chooses over all ``E``, the weights are renormalised over all a token
chose, and only the held experts' terms are summed. What the other shares
would add is left out, as in the program. The attention, the dense MLP,
the router, the shared expert and the norms are whole.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``rms_norm_eps``, ``rope_theta``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor`` and ``experts_held_first``
(0 where absent). The head counts and ``v_head_dim`` are the parameter
tree's shapes.

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
# [heads, block, s] float32, 268 MB at 32 heads and 8192 positions.
QUERY_BLOCK = 256


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


# --------------------------------------------------------- latent attention

def rotary_pairs(x, theta):
    """``x [s, .., e]``: channels ``(2j, 2j + 1)`` of the last axis turned
    by ``t theta^(-2j / e)``, ``t`` the index along the first axis."""
    s, e = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # [s, e/2]
    angles = angles.reshape((s,) + (1,) * (x.ndim - 2) + (e // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(h, p, config):
    """One sequence ``h [s, d]`` through the latent attention whose
    parameters ``p`` holds."""
    s, eps = h.shape[0], config["rms_norm_eps"]
    r, n = config["kv_lora_rank"], config["qk_nope_head_dim"]
    e, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    q = jnp.einsum("sd,dhk->shk", h, p["q_proj"])          # [s, H, n + e]
    down = h @ p["kv_down"]                                # [s, r + e]
    c = _rmsnorm(down[:, :r], p["kv_norm"], eps)
    up = jnp.einsum("sr,rhk->shk", c, p["kv_up"])          # [s, H, n + v]
    k_n, v = up[..., :n], up[..., n:]
    q_n, q_r = q[..., :n], rotary_pairs(q[..., n:], theta)
    k_r = rotary_pairs(down[:, r:], theta)                 # [s, e]: no head
    scale = 1.0 / math.sqrt(n + e)
    block = min(QUERY_BLOCK, s)
    pad = -s % block

    @jax.checkpoint
    def queries(args):
        q_n, q_r, at = args                 # [block, H, n], [block, H, e]
        scores = (jnp.einsum("qhn,khn->hqk", q_n, k_n)
                  + jnp.einsum("qhe,ke->hqk", q_r, k_r)) * scale
        seen = jnp.arange(s)[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khv->qhv", probs, v)

    blocks = lambda t: jnp.pad(
        t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            -1, block, *t.shape[1:])
    ctx = jax.lax.map(queries, (blocks(q_n), blocks(q_r),
                                blocks(jnp.arange(s))))
    ctx = ctx.reshape(-1, *ctx.shape[2:])[:s]              # [s, H, v]
    return jnp.einsum("shv,hvd->sd", ctx, p["o_proj"])


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
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    weights = weights * config["routed_scaling_factor"]

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
    eps = config["rms_norm_eps"]
    x = params["embedding"][tokens]
    routing = []
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = _rmsnorm(x, p["norm"]["scale"], eps)
        if "mla" in p:
            out = latent_attention(h, p["mla"], config)
        elif "mlp" in p:
            out = dense_mlp(h, p["mlp"])
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
