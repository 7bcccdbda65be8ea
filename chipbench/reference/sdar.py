"""Plain reference of the decoder the ``sdar`` family trains (JetLM's SDAR,
``model_type`` ``sdar_moe``: the Qwen3-MoE layer trained by block
diffusion, Arriola et al., arXiv:2503.09573): forward pass, loss and
gradients in float32 ``jax.numpy``, no kernel, no sort, no grouped product,
no rounds, no chunked loss, no flax. It reads the package's parameter tree
as data and shares no code with ``horovod_tpu``; ``jax.grad`` of it is the
reference gradient. (The expert layer's equations are those of
``chipbench/reference/mellum.py``, another softmax top-k router
renormalised over the chosen; its functions are used as they stand.)

One sequence ``x`` of ``L`` ids in blocks of ``B``, ``blk(i) = i // B``, and
its noised copy ``x~`` (``x~_i`` the mask id where ``m_i``, else ``x_i``;
the batch brings both, with the weights ``w_i = m_i / t_blk(i)``). ``d`` the
hidden size, every norm ``x rsqrt(mean x^2 + eps) w``, no bias anywhere:

    the model runs once on 2 L rows, r = 0 .. 2 L - 1: the clean copy and
      then the noised one; row r is of the half ``r >= L`` and of the
      position ``pos(r) = r mod L``
    x_0 = E[[x ; x~]]
    a decoder layer: h = x + attn(N1(x)), y = h + moe(N2(h)): two entries
      of the package's tree, each ``x += mixer(norm(x))``
    attn, on u = N1(x), H query heads on H_kv key-value heads of e: q = u
      W_q, k = u W_k, v = u W_v; q and k **normed a head** (one weight of e
      for all heads, Qwen3's q_norm and k_norm); both turned by the plain
      rotary over the halves of e (channel j of the first half against
      channel j of the second) by ``pos(r) theta_j``, theta_j =
      rope_theta^(-2j / e): **both copies of position i are turned at i**;
      score_h(r, c) = q_h(r) . k_{h // (H / H_kv)}(c) e^-1/2 over the keys
      c that row r sees, **one masked softmax over whole rows of 2 L keys,
      the mask built from (half, position)**:
          a clean row r sees the clean keys c with blk(c) <= blk(r)
            (block-causal: the whole of its own block, both directions);
          a noised row r sees the clean keys c with blk(c) < blk(r) and
            the noised keys c with blk(c) == blk(r);
          nothing else: no noised key of another block, no clean key of
            the row's own block or a later one;
      out = (softmax v) W_o
    moe: p = softmax(z W_r) over all E experts; a token's experts are the k
      largest of p; its weights those p divided by their sum
      (``norm_topk_prob``); out = sum over its experts e *that this share
      holds* of w_e down_e(silu(gate_e(z)) * up_e(z))
    logits~ = norm(x[L:]) lm_head^T (untied), over the vocabulary held and
      **the L noised rows alone**
    loss = 1 / (b L) sum_i w_i CE(logits~_i, x_i): the target of position i
      is id i itself (no shift); b sequences

**A chip's share**, as ``reference/mellum.py`` has it: the router scores
and chooses over all ``E``, the weights are renormalised over all a token
chose, only the held experts' terms are summed.

``config`` is the configuration file's dict; read from it under the
source's names: ``rms_norm_eps``, ``rope_theta``, ``num_experts_per_tok``,
``norm_topk_prob``, ``experts_held_first`` and ``block_length``.
The head counts and widths are the parameter tree's shapes. The batch is
the family's: ``tokens [n, 2 L]``, ``targets [n, L]``, ``weights [n, L]``.

No term couples two sequences, so a sequence is walked at a time
(``lax.map``) with the attention's query rows in blocks and the experts
each in turn under ``jax.checkpoint``: directives about memory that change
no value. The head's logits are held whole (``[L, V]`` float32).

A TPU multiplies float32 matrices in bf16 passes unless told otherwise, so
every entry point runs under ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.mellum import _rmsnorm, experts_layer

# Query rows whose scores against all 2 L keys the attention holds at once:
# [heads, block, 2 L] float32, 268 MB at 32 heads and 16,384 keys.
QUERY_BLOCK = 128


def block_length(config) -> int:
    return int(config["block_length"])


def rotary_halves(x, at, theta):
    """``x [r, H, e]`` turned at the positions ``at [r]``: channel ``j`` of
    the first half against ``j`` of the second by ``at theta_j``."""
    e = x.shape[-1]
    j = np.arange(e // 2, dtype=np.float64)
    freqs = jnp.asarray(float(theta) ** (-2.0 * j / e), jnp.float32)
    angles = at.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def seen(rows, length, size):
    """``[len(rows), 2 length]``: which of the ``2 length`` keys (the clean
    copy and then the noised one) the query rows ``rows`` see, blocks of
    ``size`` positions."""
    keys = jnp.arange(2 * length)[None, :]
    rows = rows[:, None]
    noised_row, noised_key = rows >= length, keys >= length
    row_block = (rows % length) // size
    key_block = (keys % length) // size
    return jnp.where(
        noised_row,
        (~noised_key & (key_block < row_block))
        | (noised_key & (key_block == row_block)),
        ~noised_key & (key_block <= row_block))


def attention(u, p, config):
    """One sequence's ``2 L`` rows ``u [2 L, d]`` (the layer's normed
    input, clean and then noised) through the attention whose parameters
    ``p`` holds."""
    rows, eps = u.shape[0], config["rms_norm_eps"]
    length, size = rows // 2, block_length(config)
    e = p["k"]["kernel"].shape[-1]
    q = jnp.einsum("sd,dhe->she", u, p["q"]["kernel"])     # [2 L, H, e]
    k = jnp.einsum("sd,dhe->she", u, p["k"]["kernel"])     # [2 L, H_kv, e]
    v = jnp.einsum("sd,dhe->she", u, p["v"]["kernel"])
    q = _rmsnorm(q, p["q_norm"]["scale"], eps)
    k = _rmsnorm(k, p["k_norm"]["scale"], eps)
    at = jnp.arange(rows) % length
    q = rotary_halves(q, at, config["rope_theta"])
    k = rotary_halves(k, at, config["rope_theta"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, rows)
    assert rows % block == 0, (rows, block)

    @jax.checkpoint
    def queries(args):
        q, mine = args                                     # [block, H, e]
        scores = jnp.einsum("qhe,khe->hqk", q, k) / math.sqrt(e)
        probs = jax.nn.softmax(
            jnp.where(seen(mine, length, size)[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khe->qhe", probs, v)

    o = jax.lax.map(queries, (q.reshape(-1, block, *q.shape[1:]),
                              jnp.arange(rows).reshape(-1, block)))
    o = o.reshape(rows, *o.shape[2:])                      # [2 L, H, e]
    return jnp.einsum("she,hed->sd", o, p["o"]["kernel"])


def _n_layers(params) -> int:
    return sum(1 for name in params if name.startswith("block_"))


def _hidden(params, tokens, config, forced):
    """One sequence ``tokens [2 L]``: ``(the L noised rows after the final
    norm, routing of every expert layer)``."""
    eps = config["rms_norm_eps"]
    x = params["embedding"][tokens]
    routing = []
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = _rmsnorm(x, p["norm"]["scale"], eps)
        if "attn" in p:
            out = attention(h, p["attn"], config)
        else:
            out, layer = experts_layer(
                h, p["moe"], config,
                None if forced is None else forced[len(routing)])
            routing.append(layer)
        x = x + out
    noised = x[tokens.shape[0] // 2:]
    return _rmsnorm(noised, params["ln_f"]["scale"], eps), routing


def _sequence(params, one, config, forced):
    tokens, targets, weights = one
    x, routing = _hidden(params, tokens, config, forced)
    logits = x @ params["lm_head"].T                       # [L, V]
    picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    each = jax.nn.logsumexp(logits, -1) - picked
    return jnp.sum(weights * each) / targets.shape[0], routing


def _loss(params, batch, config, forced_experts):
    """``batch`` the family's -> ``(loss, routing)``; ``routing`` one entry
    an expert layer, ``T = n x 2 L`` sequence-major, as ``forced_experts``
    (one ``[T, k]`` an expert layer) is."""
    n, rows = batch["tokens"].shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    forced = None if forced_experts is None else [
        f.reshape(n, rows, -1) for f in forced_experts]
    one = jax.checkpoint(lambda args: _sequence(
        params, args[0], config, args[1]))
    each, routing = jax.lax.map(one, ((
        batch["tokens"], batch["targets"],
        batch["weights"].astype(jnp.float32)), forced))
    return jnp.mean(each), jax.tree.map(
        lambda a: a.reshape(n * rows, *a.shape[2:]), routing)


def loss(params, batch, config, forced_experts=None):
    """``(training loss of the batch, routing)``."""
    with jax.default_matmul_precision("highest"):
        value, routing = jax.jit(
            lambda p, t, f: _loss(p, t, config, f))(
                params, batch, forced_experts)
        return float(value), routing


def loss_and_grad(params, batch, config, forced_experts=None):
    """``((loss, routing), float32 gradient)`` of the same."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t, f: _loss(p, t, config, f), has_aux=True))(
                params, batch, forced_experts)


def logits(params, tokens, config):
    """``[n, L, V]``: the noised rows' logits of ``tokens [n, 2 L]``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return jax.jit(lambda p, t: jax.lax.map(
            lambda one: _hidden(p, one, config, None)[0] @ p["lm_head"].T,
            t))(params, tokens)


def mixer(u, p, config):
    """The attention mixer's output on its own input ``u [n, 2 L, d]``, a
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda u, p: jax.lax.map(
            lambda one: attention(one, p, config), u.astype(jnp.float32)))(
                u, jax.tree.map(lambda a: a.astype(jnp.float32), p))
