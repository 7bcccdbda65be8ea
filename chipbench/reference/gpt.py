"""Plain reference of the decoder the ``gpt`` family runs: forward pass
and loss in float32 ``jax.numpy``, no kernel, no recomputation, no flax.
It reads the package's parameter tree as data and shares no code with
``horovod_tpu.models``; ``jax.grad`` of it is the reference gradient.

The block, as the package builds it (departures from GPT-2 noted in the
configuration file): x += attn(rmsnorm(x)); x += mlp(rmsnorm(x)); rotary
positions on q and k (first half / second half pairing, base 10000);
causal softmax attention scaled by head_dim^-1/2; gelu (tanh form)
between two bias-free projections; tied embedding for the logits; loss =
mean cross-entropy of position t against token t+1 over the first s-1
positions.

A TPU multiplies float32 matrices in bf16 passes unless told otherwise,
so every entry point runs under ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x):                     # [s, h, hd]
    s, _, hd = x.shape
    half = hd // 2
    freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p):                   # x [s, d], one sequence
    s = x.shape[0]
    h = _rmsnorm(x, p["ln1"]["scale"])
    q = _rotary(jnp.einsum("sd,dhk->shk", h, p["attn"]["q"]["kernel"]))
    k = _rotary(jnp.einsum("sd,dhk->shk", h, p["attn"]["k"]["kernel"]))
    v = jnp.einsum("sd,dhk->shk", h, p["attn"]["v"]["kernel"])
    scores = jnp.einsum("qhk,thk->hqt", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    ctx = jnp.einsum("hqt,thk->qhk", probs, v)
    x = x + jnp.einsum("qhk,hkd->qd", ctx, p["attn"]["o"]["kernel"])
    h = _rmsnorm(x, p["ln2"]["scale"])
    h = _gelu_tanh(h @ p["mlp"]["up"]["kernel"])
    return x + h @ p["mlp"]["down"]["kernel"]


def _head_loss(x, scale, emb, tokens):
    logits = _rmsnorm(x, scale)[:-1] @ emb.T
    picked = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _n_layers(params) -> int:
    return sum(1 for name in params if name.startswith("block_"))


def sequence_loss(params, tokens):
    """Loss of ONE sequence ``tokens [s]`` under ``params`` (the
    package's tree: embedding, block_0.., ln_f)."""
    params = _f32(params)
    x = params["embedding"][tokens]
    for i in range(_n_layers(params)):
        x = _block(x, params[f"block_{i}"])
    return _head_loss(x, params["ln_f"]["scale"], params["embedding"],
                      tokens)


def loss(params, tokens) -> float:
    """Mean of ``sequence_loss`` over the sequences of ``tokens [n, s]``,
    run one sequence and one block at a time: three small programs
    however deep the model is, and the float32 scores of one block of one
    sequence are all that is alive at once."""
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda emb, t: _f32(emb)[t])
        block = jax.jit(lambda x, p: _block(x, _f32(p)))
        head = jax.jit(lambda x, scale, emb, t: _head_loss(
            x, _f32(scale), _f32(emb), t))
        total = 0.0
        for t in tokens:
            x = embed(params["embedding"], t)
            for i in range(_n_layers(params)):
                x = block(x, params[f"block_{i}"])
            total += float(head(x, params["ln_f"]["scale"],
                                params["embedding"], t))
        return total / tokens.shape[0]


def loss_and_grad(params, tokens):
    """The same mean loss and its float32 gradient, accumulated one
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(jax.value_and_grad(sequence_loss))
        total = None
        for t in tokens:
            one = fn(params, t)
            total = one if total is None else jax.tree.map(
                jnp.add, total, one)
        return jax.tree.map(lambda a: a / tokens.shape[0], total)
