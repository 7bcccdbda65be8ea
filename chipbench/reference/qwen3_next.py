"""Plain reference of the hybrid decoder the ``qwen3_next`` family runs:
forward pass, loss and gradients in float32 ``jax.numpy``, no kernel, no
sort, no grouped product, no chunking of the delta rule, no flax. It reads
the package's parameter tree as data and shares no code with
``horovod_tpu.models``; ``jax.grad`` of it is the reference gradient.

The equations are those of Hugging Face's ``modeling_qwen3_next`` (the
configuration file lists the departures). A layer is one mixer behind one
norm with a residual, ``x += mixer(norm(x))``, the norm ``x rsqrt(mean x^2
+ eps) (1 + w)``; which mixer, the parameter tree says (a block holds
``gdn``, ``attn`` or ``moe``):

    gdn (Gated DeltaNet): [q | k | v | z] = u W_qkvz, [b | a] = u W_ba;
      [q | k | v] = silu(conv([q | k | v])), a causal depthwise
      convolution over the sequence, no bias; beta = sigmoid(b), g =
      -exp(A_log) softplus(a + dt_bias) a value head; q and k a head
      x / sqrt(sum x^2 + 1e-6), q then times d_k^-1/2; key head j serves
      value heads [j r, (j + 1) r); a state S [d_k, d_v] a value head,
      **position by position**:
        S' = exp(g_t) S_{t-1}
        S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
        o_t = S_t^T q_t
      y = rmsnorm(o) w * silu(z) a head (w from one, plain scale; the
      norm before the gate); out = y W_out
    attn: [q | gate] a head = h Wq, k, v = h Wk, h Wv; q and k the norm
      above over a head's channels, one vector for all heads; the first
      ``partial_rotary_factor`` of a head's channels rotated (halves of
      that width against each other, base ``rope_theta``); query head i
      reads key-value head i // (heads / kv heads); causal softmax of
      q k^T / sqrt(head_dim); out = (attn * sigmoid(gate)) Wo
    moe: p = softmax(h Wr) over all E experts; a token's experts are its
      k largest p; its weights those p divided by their sum
      (``norm_topk_prob``); routed = sum over its experts e *that this
      share holds* of w_e down_e(silu(gate_e(h)) * up_e(h)); out = routed
      + sigmoid(h w_g) shared_down(silu(shared_gate(h)) * shared_up(h))
    logits = norm(x) lm_head^T (untied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions

**A chip's share.** The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``: the router scores
and chooses over all ``E``, the weights are renormalised over all a token
chose, and only the held experts' terms are summed. What the other shares
would add is left out, as in the program. The mixers, the router and the
shared expert are whole.

``config`` is the configuration file's dict; read from it, under the
source's key names: ``rms_norm_eps``, ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``head_dim``, ``partial_rotary_factor``,
``rope_theta``, ``num_experts_per_tok``, ``norm_topk_prob`` and
``experts_held_first`` (0 where absent).

No term of the loss couples two sequences, so a sequence is walked at a
time (``lax.map``) with the heads, the experts, the head's positions and
runs of the rule's positions each in turn under ``jax.checkpoint``: a
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
# Positions of the rule whose states the backward pass keeps at once (a
# state is 2 MiB at 32 heads of 128 x 128).
RULE_BLOCK = 128


def _rmsnorm(x, weight, eps):
    """The family's norm: the gain is ``1 + weight``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + weight)


# ------------------------------------------------------------ Gated DeltaNet

def _conv(x, weight):               # x [s, c], weight [taps, c]
    taps, s = weight.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j         # tap j reads position t - back
        moved = jnp.concatenate([jnp.zeros_like(x[:back]), x[:s - back]], 0)
        out = out + weight[j] * moved
    return jax.nn.silu(out)


def delta_rule(q, k, v, g, beta):
    """``q``, ``k`` ``[s, H, d_k]``, ``v [s, H, d_v]``, ``g``, ``beta``
    ``[s, H]`` -> ``o [s, H, d_v]``, one position after another from a
    zero state."""
    s, heads, d_k = q.shape

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[:, None, None] * state
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


def gdn_mixer(u, p, config):
    """One sequence ``u [s, d]`` through the Gated DeltaNet mixer whose
    parameters ``p`` holds; the rule one position after another."""
    key_heads, value_heads = (config["linear_num_key_heads"],
                              config["linear_num_value_heads"])
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    keys, values = key_heads * d_k, value_heads * d_v
    qkv, z = jnp.split(u @ p["in_proj_qkvz"], [2 * keys + values], -1)
    b, a = jnp.split(u @ p["in_proj_ba"], 2, -1)
    q, k, v = jnp.split(_conv(qkv, p["conv_kernel"]), [keys, 2 * keys], -1)
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    to_value_heads = lambda t: jnp.repeat(
        unit(t.reshape(-1, key_heads, d_k)), value_heads // key_heads, axis=1)
    q, k = to_value_heads(q) / math.sqrt(d_k), to_value_heads(k)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = delta_rule(q, k, v.reshape(-1, value_heads, d_v), g, beta)
    normed = o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + config["rms_norm_eps"])
    y = normed * p["norm_scale"] * jax.nn.silu(
        z.reshape(-1, value_heads, d_v))
    return y.reshape(-1, values) @ p["out_proj"]


# ---------------------------------------------------------------- attention

def _rotary(x, width, theta):       # x [n, s, hd]: the first width turned
    half = width // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


@jax.checkpoint
def _one_head(qkv):                 # three of [s, hd]
    q, k, v = qkv
    s = q.shape[0]
    scores = (q @ k.T) / math.sqrt(q.shape[-1])
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1) @ v


def attention(h, p, config):        # h [s, d]
    d, eps = h.shape[-1], config["rms_norm_eps"]
    hd = config["head_dim"]
    heads, kv_heads = p["q"]["kernel"].shape[1], p["k"]["kernel"].shape[1]
    split = lambda name, n: jnp.moveaxis(
        (h @ p[name]["kernel"].reshape(d, -1)).reshape(h.shape[0], n, -1),
        1, 0)                                           # [n, s, ...]
    q, gate = jnp.split(split("q", heads), 2, -1)       # [query | gate]
    k, v = split("k", kv_heads), split("v", kv_heads)
    width = int(config["partial_rotary_factor"] * hd)
    q = _rotary(_rmsnorm(q, p["q_norm"]["scale"], eps), width,
                config["rope_theta"])
    k = _rotary(_rmsnorm(k, p["k_norm"]["scale"], eps), width,
                config["rope_theta"])
    k, v = (jnp.repeat(t, heads // kv_heads, axis=0) for t in (k, v))
    ctx = jax.lax.map(_one_head, (q, k, v)) * jax.nn.sigmoid(gate)
    return jnp.moveaxis(ctx, 0, 1).reshape(h.shape[0], -1) @ p["o"][
        "kernel"].reshape(-1, d)


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

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        p["gate"], p["up"], p["down"], weights[:, first:first + held].T))
    shared = (jax.nn.silu(h @ p["shared_gate"]) * (h @ p["shared_up"])
              ) @ p["shared_down"]
    return routed + jax.nn.sigmoid(h @ p["shared_expert_gate"]) * shared, {
        "probs": probs, "own": own, "used": experts}


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
    eps = config["rms_norm_eps"]
    x = params["embedding"][tokens]
    routing = []
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = _rmsnorm(x, p["norm"]["scale"], eps)
        if "gdn" in p:
            out = gdn_mixer(h, p["gdn"], config)
        elif "attn" in p:
            out = attention(h, p["attn"], config)
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
