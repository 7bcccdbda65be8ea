"""Plain reference of the decoder the ``keye_vl2`` family runs (the
language model of Keye-VL-2.0; no vision tower): forward pass, both losses
and gradients in float32 ``jax.numpy``, no kernel, no sort but
``jax.lax.top_k``, no grouped product, no rounds, no flax. It reads the
package's parameter tree as data and imports nothing from ``horovod_tpu``;
``jax.grad`` of it is the reference gradient.

A decoder layer of the source is ``h = x + attn(norm(x))``, ``y = h +
moe(norm(h))``, two entries of the package's tree, each ``x +=
mixer(norm(x))`` with the norm ``x rsqrt(mean x^2 + eps) w``; which mixer,
the parameter tree says (a block holds ``dsa`` or ``moe``):

    dsa (grouped-query attention over the keys a learned indexer chooses;
      DeepSeek's published sparse attention at the sizes of ``sa_config``):
      main attention: q = h W_q [s, H, 128], k = h W_k, v = h W_v [s, H_kv,
        128]; q and k RMS-normalised a head (one weight for all query
        heads, one for all key heads); rotary at ``rope_theta`` over the
        halves of the 128; score_h(t, u) = q_h(t) . k_{h // g}(u) 128^-1/2
        **for u in S_t only**, softmax over S_t, o_h(t) = sum_{u in S_t}
        p_h(t, u) v_{h // g}(u); out = [o_1 .. o_H] W_o
      the indexer, on x~ = stop_gradient(h): q^I = x~ W_qI [s, J, e];
        k^I = LayerNorm_e(x~ W_kI) [s, e] (a scale and a bias), **one
        index key a position**; rotary at the same base over the halves of
        the e, on both; w = x~ W_w J^-1/2 e^-1/2 [s, J];
        I(t, u) = sum_j w_j(t) relu(q^I_j(t) . k^I(u)) for u <= t
      S_t: every u <= t while t < topk; from there on the ``topk`` u <= t
        with the largest I(t, u), equal scores to the lower u
        (``jax.lax.top_k``'s order). **The chosen keys are a mask
        scattered from ``top_k``'s indices** (not a gather: the same sum
        over the same keys)
      the indexer's loss: pbar(t, u) = 1/H sum_h p_h(t, u) on S_t,
        detached; r(t, .) = softmax over S_t of I(t, .);
        L_I = 1/s sum_t sum_{u in S_t} pbar (log pbar - log r), 0 log 0 = 0
    moe: s = softmax(h W_r) over all E experts; a token's experts are the
      k largest of s; its weights those s divided by their sum + 1e-20
      (``norm_topk_prob``); out = sum over its experts e *that this share
      holds* of w_e down_e(silu(gate_e(h)) * up_e(h)); no shared expert
    logits = norm(x) lm_head^T (untied), over the vocabulary held
    loss = mean cross-entropy of position t against token t+1 over the
      first s-1 positions, plus the layers' L_I averaged over the batch

**A chip's share.** The expert stacks hold ``count`` experts, numbers
``experts_held_first`` and up of the router's ``E``; what the other shares
would add is left out, as in the program. The attention, the indexer, the
router and the norms are whole.

``config`` is the configuration file's dict; read from it: ``rms_norm_eps``,
``rope_theta``, ``sa_config`` (``topk``), ``num_experts_per_tok``,
``norm_topk_prob`` and ``experts_held_first`` (0 where absent). Head counts
and widths are the parameter tree's shapes.

It can be handed the program's experts (``forced_experts``) and the
program's choice of keys (``forced_keys``, a ``[s, s]`` mask a layer) for
the comparisons in which a near-tie must not decide.

No term of the loss couples two sequences, so a sequence is walked at a
time (``lax.map``) with the queries of the attention in blocks, the
experts and the head's positions each in turn under ``jax.checkpoint``: a
directive about memory that changes no value. Every entry point runs under
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Positions whose logits the loss holds at once.
HEAD_BLOCK = 2048
# Queries whose scores against every key the attention holds at once:
# [heads, block, s] float32, 268 MB at 32 heads and 16384 positions.
QUERY_BLOCK = 128


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rotary_halves(x, theta):
    """``x [s, .., e]``: channel ``j`` of the first half of the last axis
    against channel ``j`` of the second, turned by ``t theta^(-2j / e)``,
    ``t`` the index along the first axis."""
    s, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    angles = angles.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# --------------------------------------------------------- sparse attention

def index_parts(h, p, config):
    """The indexer's three products on ``h [s, d]`` (already detached):
    ``(q^I [s, J, e], k^I [s, e], w [s, J])``."""
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    heads, width = p["index_q"].shape[1:]
    q = rotary_halves(jnp.einsum("sd,dje->sje", h, p["index_q"]), theta)
    k = _layernorm(h @ p["index_k"], p["index_k_norm"][0],
                   p["index_k_norm"][1], eps)
    k = rotary_halves(k, theta)
    w = (h @ p["index_w"]) / math.sqrt(heads) / math.sqrt(width)
    return q, k, w


def choose(scores, at, topk):
    """``scores [block, s]`` of queries at positions ``at [block]`` ->
    the mask ``[block, s]`` of ``S_t``: ``jax.lax.top_k`` of the causal
    row, its indices scattered into a mask, nothing above ``t``."""
    s = scores.shape[-1]
    causal = jnp.arange(s)[None, :] <= at[:, None]
    # -0.0 as 0.0: equal scores are equal, whatever order a sort gives
    # the two patterns
    scores = jnp.where(scores == 0, 0.0, scores)
    _, index = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                             min(topk, s))
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, index].set(True) & causal


def sparse_attention(h, p, config, forced=None, keep=False):
    """One sequence ``h [s, d]`` through the mixer whose parameters ``p``
    holds: ``(out [s, d], L_I, kept)``. ``forced [s, s]`` puts another
    program's choice of keys in place of this one's. ``kept`` (``keep``):
    the index scores ``[s, s]`` (``-inf`` above the diagonal) and this
    reference's own choice."""
    s, eps = h.shape[0], config["rms_norm_eps"]
    theta, topk = float(config["rope_theta"]), config["sa_config"]["topk"]
    width = p["q_proj"].shape[-1]
    group = p["q_proj"].shape[1] // p["k_proj"].shape[1]
    q = jnp.einsum("sd,dhk->shk", h, p["q_proj"])
    k = jnp.einsum("sd,dhk->shk", h, p["k_proj"])
    v = jnp.einsum("sd,dhk->shk", h, p["v_proj"])
    q = rotary_halves(_rmsnorm(q, p["q_norm"], eps), theta)
    k = rotary_halves(_rmsnorm(k, p["k_norm"], eps), theta)
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    q_i, k_i, w = index_parts(jax.lax.stop_gradient(h), p, config)
    block = min(QUERY_BLOCK, s)
    pad = -s % block

    @jax.checkpoint
    def queries(args):
        q, q_i, w, at, forced = args
        index = jnp.einsum("qj,qjk->qk", w, jax.nn.relu(
            jnp.einsum("qje,ke->qjk", q_i, k_i)))           # [block, s]
        causal = jnp.arange(s)[None, :] <= at[:, None]
        index = jnp.where(causal, index, -jnp.inf)
        own = choose(index, at, topk)
        seen = own if forced is None else forced
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(width)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        probs = jnp.where(seen[None], probs, 0.0)
        ctx = jnp.einsum("hqk,khv->qhv", probs, v)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=0))
        log_r = jax.nn.log_softmax(jnp.where(seen, index, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(target > 0, target * (
            jnp.log(jnp.where(target > 0, target, 1.0))
            - jnp.where(seen, log_r, 0.0)), 0.0), -1)
        return ctx, kl, ((index, own) if keep else None)

    blocks = lambda t: jnp.pad(
        t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            -1, block, *t.shape[1:])
    # a padded query sits at position 0 and is dropped below
    ctx, kl, kept = jax.lax.map(queries, (
        blocks(q), blocks(q_i), blocks(w), blocks(jnp.arange(s)),
        None if forced is None else blocks(forced)))
    unblock = lambda t: t.reshape(-1, *t.shape[2:])[:s]
    out = jnp.einsum("shv,hvd->sd", unblock(ctx), p["o_proj"])
    return out, jnp.sum(unblock(kl)) / s, jax.tree.map(unblock, kept)


# ------------------------------------------------------------------ experts

def route(h, router, k):
    """``h [T, d]`` -> ``(probs [T, E], experts [T, k])``."""
    probs = jax.nn.softmax(h @ router, axis=-1)
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
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)

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


def _sequence(params, tokens, config, forced_experts, forced_keys):
    """One sequence ``tokens [s]``: ``(cross entropy, the layers' L_I
    summed, routing of every expert layer)``."""
    eps = config["rms_norm_eps"]
    x = params["embedding"][tokens]
    routing, index_loss, mixers = [], 0.0, 0
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = _rmsnorm(x, p["norm"]["scale"], eps)
        if "dsa" in p:
            out, kl, _ = sparse_attention(
                h, p["dsa"], config,
                None if forced_keys is None else forced_keys[mixers])
            index_loss, mixers = index_loss + kl, mixers + 1
        else:
            out, layer = experts_layer(
                h, p["moe"], config, None if forced_experts is None
                else forced_experts[len(routing)])
            routing.append(layer)
        x = x + out
    x = _rmsnorm(x, params["ln_f"]["scale"], eps)
    return (_cross_entropy(x, params["lm_head"], tokens), index_loss,
            routing)


def _loss(params, tokens, config, forced_experts, forced_keys):
    """``tokens [n, s]`` -> ``(L_LM + sum of L_I, (L_LM, sum of L_I,
    routing))``; ``routing`` one entry an expert layer, ``T = n x s``
    sequence-major, as ``forced_experts`` (one ``[T, k]`` an expert layer)
    is; ``forced_keys`` one ``[n, s, s]`` mask a mixer."""
    n, s = tokens.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    forced = None if forced_experts is None else [
        f.reshape(n, s, -1) for f in forced_experts]
    one = jax.checkpoint(lambda args: _sequence(
        params, args[0], config, args[1], args[2]))
    ce, index_loss, routing = jax.lax.map(one, (tokens, forced, forced_keys))
    ce, index_loss = jnp.mean(ce), jnp.mean(index_loss)
    return ce + index_loss, (ce, index_loss, jax.tree.map(
        lambda a: a.reshape(n * s, *a.shape[2:]), routing))


def loss(params, tokens, config, forced_experts=None, forced_keys=None):
    """``(training loss of the batch tokens [n, s], (L_LM, L_I,
    routing))``."""
    with jax.default_matmul_precision("highest"):
        value, parts = jax.jit(
            lambda p, t, f, g: _loss(p, t, config, f, g))(
                params, tokens, forced_experts, forced_keys)
        return float(value), parts


def loss_and_grad(params, tokens, config, forced_experts=None,
                  forced_keys=None):
    """``((loss, (L_LM, L_I, routing)), float32 gradient)`` of the same."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t, f, g: _loss(p, t, config, f, g), has_aux=True))(
                params, tokens, forced_experts, forced_keys)


def mixer(h, p, config, forced=None):
    """``h [n, s, d]`` through one mixer, a sequence at a time, keeping
    the index scores and the choice: ``(out, L_I a sequence, (index
    scores, own choice))``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda h, p, f: jax.lax.map(
            lambda args: sparse_attention(args[0], p, config, args[1],
                                          keep=True), (h, f)))(
                h.astype(jnp.float32),
                jax.tree.map(lambda a: a.astype(jnp.float32), p), forced)
