"""Plain reference of the hybrid decoder the ``kimi_linear`` family runs:
forward pass, loss and gradients in float32 ``jax.numpy``, no kernel, no
sort, no grouped product, no chunking of the delta rule, no flax. It reads
the package's parameter tree as data and shares no code with
``horovod_tpu.models``; ``jax.grad`` of it is the reference gradient.

The equations are those of Kimi Linear (arXiv:2510.26692) at the keys its
``config.json`` gives (the configuration file lists the departures). A
decoder layer of the source is ``h = x + mixer(norm(x))``, ``y = h +
ffn(norm(h))``, two entries of the package's tree, each ``x +=
mixer(norm(x))`` with the norm ``x rsqrt(mean x^2 + eps) w``; which mixer,
the parameter tree says (a block holds ``kda``, ``mla``, ``mlp`` or
``moe``):

    kda (Kimi Delta Attention): q, k, v = u W_q, u W_k, u W_v (the
      thirds of W_qkv's columns); each silu(conv(.)), a causal depthwise
      convolution over the sequence, no bias; beta = sigmoid(u W_beta) a head; g = -exp(A_log) softplus((u
      W_fa) W_fb + dt_bias), **a number a head and key channel** (A_log a
      head, dt_bias a channel); q and k a head x / sqrt(sum x^2 + 1e-6),
      q then times d_h^-1/2; a state S [d_h, d_h] a head, **position by
      position**:
        S' = Diag(exp(g_t)) S_{t-1}       (row c of S times exp(g_t,c))
        S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
        o_t = S_t^T q_t
      y = rmsnorm(o) w * sigmoid((u W_ga) W_gb) a head (w from one, one
      vector for all heads; the norm before the gate); out = y W_out
    mla (multi-head latent attention, ``q_lora_rank`` null,
      ``mla_use_nope``): q = h W_q, a head's n + e columns q_n | q_r;
      [c | k_r] = h W_kva (r + e columns); c <- norm(c) with a weight [r];
      [k_n | v] a head = c W_kvb (n + v columns a head); k_r is **one**
      e-vector a position for every head; **nothing is rotated**.
      score_h(t, u) = (q_n,h(t) . k_n,h(u) + q_r,h(t) . k_r(u)) (n +
      e)^-1/2, causal softmax, o_h = sum_u p v_h(u); out = [o_1 .. o_H] W_o
    mlp: down(silu(gate(h)) * up(h))
    moe: s = sigmoid(h W_r) over all E experts; a token's experts are the
      k largest of s + b (b a buffer, no gradient; ``num_expert_group`` 1
      and ``topk_group`` 1, so the groups are no limit); its weights those
      s divided by their sum + 1e-20 (``moe_renormalize``), times
      ``routed_scaling_factor``; out = sum over its experts e *that this
      share holds* of w_e down_e(silu(gate_e(h)) * up_e(h)), plus the
      shared expert, one ungated SwiGLU that every token passes
    logits = norm(x) lm_head^T (untied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions

**A chip's share.** The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``: the router scores and
chooses over all ``E``, the weights are renormalised over all a token
chose, and only the held experts' terms are summed. What the other shares
would add is left out, as in the program. The mixers, the dense MLP, the
router, the shared expert and the norms are whole.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``rms_norm_eps``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``linear_attn_config``
(``num_heads``, ``head_dim``), ``num_experts_per_token``,
``moe_renormalize``, ``routed_scaling_factor`` and ``experts_held_first``
(0 where absent). The latent attention's head count and ``v_head_dim`` are
the parameter tree's shapes.

No term of the loss couples two sequences, so a sequence is walked at a
time (``lax.map``) with the queries of the attention in blocks, the
experts, the head's positions and runs of the rule's positions each in
turn under ``jax.checkpoint``: a directive about memory that changes no
value.

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
# Positions of the rule whose states the backward pass keeps at once (a
# state is 2 MiB at 32 heads of 128 x 128).
RULE_BLOCK = 128


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


# ------------------------------------------------------ Kimi Delta Attention

def _conv(x, weight):               # x [s, c], weight [taps, c]
    taps, s = weight.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j         # tap j reads position t - back
        moved = jnp.concatenate([jnp.zeros_like(x[:back]), x[:s - back]], 0)
        out = out + weight[j] * moved
    return jax.nn.silu(out)


def delta_rule(q, k, v, g, beta):
    """``q``, ``k``, ``g`` ``[s, H, d_k]``, ``v [s, H, d_v]``, ``beta [s,
    H]`` -> ``o [s, H, d_v]``, one position after another from a zero
    state, the decay a row of the state."""
    s, heads, d_k = q.shape

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (
            beta_t[:, None] * (v_t - read))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    block = math.gcd(s, RULE_BLOCK)
    run = jax.checkpoint(lambda state, ats: jax.lax.scan(position, state, ats))
    _, o = jax.lax.scan(
        run, jnp.zeros((heads, d_k, v.shape[-1]), jnp.float32),
        jax.tree.map(lambda t: t.reshape(s // block, block, *t.shape[1:]),
                     (q, k, v, g, beta)))
    return o.reshape(s, heads, -1)


def kda_mixer(u, p, config):
    """One sequence ``u [s, d]`` through the Kimi Delta Attention mixer
    whose parameters ``p`` holds; the rule one position after another."""
    linear = config["linear_attn_config"]
    heads, d_h = linear["num_heads"], linear["head_dim"]
    by_head = lambda t: t.reshape(-1, heads, d_h)
    # the source's three projections and three convolutions: the columns
    # of the parameters are cut, q | k | v, and no activation is (cutting
    # the convolution's [8192, 12288] float32 result in three read 4% off
    # on a v5e, libtpu 0.0.34: PERF.md section 6, PR 55)
    q, k, v = (by_head(_conv(u @ w, taps)) for w, taps in zip(
        jnp.split(p["in_proj_qkv"], 3, -1),
        jnp.split(p["conv_kernel"], 3, -1)))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(d_h), unit(k)
    beta = jax.nn.sigmoid(u @ p["in_proj_beta"])
    g = -jnp.exp(p["A_log"])[:, None] * by_head(jax.nn.softplus(
        (u @ p["decay_down"]) @ p["decay_up"] + p["dt_bias"]))
    o = delta_rule(q, k, v, g, beta)
    normed = o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + config["rms_norm_eps"])
    z = by_head((u @ p["gate_down"]) @ p["gate_up"])
    y = normed * p["norm_scale"] * jax.nn.sigmoid(z)
    return y.reshape(-1, heads * d_h) @ p["out_proj"]


# --------------------------------------------------------- latent attention

def latent_attention(h, p, config):
    """One sequence ``h [s, d]`` through the latent attention whose
    parameters ``p`` holds: a whole key ``k_n | k_r`` a head, no rotary."""
    s, eps = h.shape[0], config["rms_norm_eps"]
    r, n = config["kv_lora_rank"], config["qk_nope_head_dim"]
    e = config["qk_rope_head_dim"]
    q = jnp.einsum("sd,dhk->shk", h, p["q_proj"])          # [s, H, n + e]
    down = h @ p["kv_down"]                                # [s, r + e]
    c = _rmsnorm(down[:, :r], p["kv_norm"], eps)
    up = jnp.einsum("sr,rhk->shk", c, p["kv_up"])          # [s, H, n + v]
    v = up[..., n:]
    k = jnp.concatenate([up[..., :n], jnp.broadcast_to(
        down[:, None, r:], (s, up.shape[1], e))], -1)      # [s, H, n + e]
    scale = 1.0 / math.sqrt(n + e)
    block = min(QUERY_BLOCK, s)
    pad = -s % block

    @jax.checkpoint
    def queries(args):
        q, at = args                        # [block, H, n + e]
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        seen = jnp.arange(s)[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khv->qhv", probs, v)

    blocks = lambda t: jnp.pad(
        t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            -1, block, *t.shape[1:])
    ctx = jax.lax.map(queries, (blocks(q), blocks(jnp.arange(s))))
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
    n_experts, k = p["router"].shape[-1], config["num_experts_per_token"]
    first = config.get("experts_held_first", 0)
    held = p["up"].shape[0]
    scores, own = route(h, p["router"], bias, k)
    experts = own if forced is None else forced
    chosen = jnp.sum(experts[..., None] == jnp.arange(n_experts), axis=1,
                     dtype=jnp.float32)                 # [T, E]
    weights = chosen * scores
    if config["moe_renormalize"]:
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
        if "kda" in p:
            out = kda_mixer(h, p["kda"], config)
        elif "mla" in p:
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
