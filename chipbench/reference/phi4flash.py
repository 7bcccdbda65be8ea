"""Plain reference of the decoder the ``phi4flash`` family runs
(Microsoft's Phi-4-mini-flash-reasoning; SambaY, arXiv:2507.06607):
forward pass, loss and gradients in float32 ``jax.numpy``, no kernel, no
chunked scan, no flax. It reads the package's parameter tree as data and
shares no code with ``horovod_tpu``; ``jax.grad`` of it is the reference
gradient.

The equations are what the keys of the catalog's ``config`` and the
paper define; what neither has a number for is the configuration file's
``assumed``. ``d`` the hidden size, ``D = 2 d``, ``N`` the state, ``R =
ceil(d / 16)``, layer ``l`` counted from 0 **in the published model** (the
file's ``first_layer`` is the number of the tree's first decoder layer):

    x_0 = E[token]; no positional term anywhere
    a decoder layer: h = x + mixer_l(LN1(x)), y = h + MLP(LN2(h)): two
      entries of the package's tree, each ``x += mixer(norm(x))``; LN is
      LayerNorm: (x - mean x) rsqrt(var x + eps) w + b
    MLP(u) = (silu(u W_gate) * (u W_up)) W_down, no bias
    which mixer, by ``layer_kind(l)``, with n the published depth:
      l <  n / 2 + 1 and l even (and l = n / 2):  Mamba-1
      l <  n / 2 + 1 and l odd:  differential attention inside a window
      l =  n / 2 + 1:            the same over every causal key; its k and
                                 v are what every later attention reads
      l >= n / 2 + 2 and l even: gated memory unit on layer n / 2's memory
      l >= n / 2 + 2 and l odd:  cross attention: a query of its own on
                                 layer n / 2 + 1's k and v
    Mamba-1, on a = LN1(x): [u | z] = a W_in; u = silu(conv(u) + b_c), the
      convolution causal and depthwise over positions (tap j of ``taps``
      reads position t - taps + 1 + j); [r | B | C] = u W_x; delta =
      softplus(r W_dt + b_dt); A = -exp(A_log);
          h_t = exp(delta_t[:, None] A) h_{t-1} + (delta_t u_t)[:, None] B_t[None, :]
          m_t = h_t C_t + D_skip u_t
      **position by position**; out = (m * silu(z)) W_out. Layer n / 2's m
      is the memory.
    differential attention, on a: q = a W_q + b_q (H heads of e), k, v
      likewise (H_kv heads), or the k and v it is handed. Pair j of H / 2:
      q1 = q[2 j], q2 = q[2 j + 1]; it reads key-value pair g = j // (H /
      H_kv): k1 = k[2 g], k2 = k[2 g + 1], V = [v[2 g] | v[2 g + 1]].
      P1 = softmax(q1 k1^T e^-1/2 + mask), P2 = softmax(q2 k2^T e^-1/2 +
      mask), **one masked softmax over whole rows, the mask built from
      positions** (s <= t, and in a windowed layer t - window < s);
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init =
      0.8 - 0.6 exp(-0.3 l); o_j = RMSNorm((P1 - lambda P2) V) w (1 -
      lambda_init), the norm over the pair's 2 e channels; out = concat_j
      (o_j) W_o + b_o.
    gated memory unit, on a: out = (m * silu(a W_1)) W_2.
    logits = LN_f(x) E^T (tied); loss: mean cross entropy of position t's
      logits against token t + 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MAMBA, WINDOWED, FULL, UNIT, CROSS = (
    "mamba", "sliding_attention", "full_attention", "gated_memory_unit",
    "cross_attention")
# Positions whose logits are held at once, and the queries a block of the
# attention takes (so that 16,384 positions fit); positions a block of the
# recurrence keeps for its backward pass (the walk inside is a position at
# a time).
HEAD_BLOCK = 2048
QUERY_BLOCK = 128
SCAN_BLOCK = 256


def layer_kind(l: int, published_layers: int) -> str:
    """The mixer of published layer ``l`` of a model of
    ``published_layers`` layers."""
    half = published_layers // 2
    if l <= half + 1:
        if l == half + 1:
            return FULL
        return WINDOWED if l % 2 else MAMBA
    return CROSS if l % 2 else UNIT


def layer_norm(x, p, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(centred * centred, -1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def recurrence(u, delta, a, b, c):
    """``u``, ``delta`` ``[s, D]``, ``a [D, N]``, ``b``, ``c`` ``[s, N]``
    -> ``[s, D]``: the state a position at a time."""
    s = u.shape[0]
    block = min(SCAN_BLOCK, s)
    pad = -s % block

    def one(h, x):
        u_t, delta_t, b_t, c_t = x
        h = jnp.exp(delta_t[:, None] * a) * h + (delta_t * u_t)[:, None] * b_t
        return h, h @ c_t

    @jax.checkpoint
    def positions(h, xs):
        return jax.lax.scan(one, h, xs)

    # a padded position has delta 0: it decays nothing and adds nothing
    blocks = lambda t: jnp.pad(t, ((0, pad), (0, 0))).reshape(
        -1, block, t.shape[-1])
    _, y = jax.lax.scan(positions, jnp.zeros_like(a),
                        tuple(map(blocks, (u, delta, b, c))))
    return y.reshape(-1, u.shape[-1])[:s]


def mamba(x, p):
    """One sequence ``x [s, d]`` (the layer's normed input) through the
    Mamba-1 mixer whose parameters ``p`` holds: ``(out [s, d], m [s, D])``,
    ``m`` before the gate."""
    s = x.shape[0]
    taps, n = p["conv_kernel"].shape[0], p["A_log"].shape[-1]
    u, z = jnp.split(x @ p["in_proj"], 2, -1)
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(p["conv_kernel"][j] * padded[j:j + s]
                        for j in range(taps)) + p["conv_bias"])
    rank = p["dt_proj"].shape[0]
    r, b, c = jnp.split(u @ p["x_proj"], [rank, rank + n], -1)
    delta = jax.nn.softplus(r @ p["dt_proj"] + p["dt_bias"])
    m = recurrence(u, delta, -jnp.exp(p["A_log"]), b, c) + p["D_skip"] * u
    return (m * jax.nn.silu(z)) @ p["out_proj"], m


def keys_values(x, p):
    """The ``k`` and ``v`` ``[s, H_kv, e]`` a self-attention layer makes
    of its normed input."""
    project = lambda w: jnp.einsum("sd,dhe->she", x, w["kernel"]) + w["bias"]
    return project(p["k"]), project(p["v"])


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def differential_attention(x, p, l, eps, window=None, kv=None):
    """One sequence ``x [s, d]`` through the differential attention whose
    parameters ``p`` holds, published layer ``l``; ``window`` keys a query
    sees (None: every causal one); ``kv``: the keys and values of another
    layer (a cross layer), else its own."""
    s = x.shape[0]
    q = jnp.einsum("sd,dhe->she", x, p["q"]["kernel"]) + p["q"]["bias"]
    k, v = keys_values(x, p) if kv is None else kv
    e, pairs = q.shape[-1], q.shape[1] // 2
    group = q.shape[1] // k.shape[1]
    q1, q2 = q[:, 0::2], q[:, 1::2]                        # [s, pairs, e]
    k1 = jnp.repeat(k[:, 0::2], group, axis=1)             # pair j: g = j // group
    k2 = jnp.repeat(k[:, 1::2], group, axis=1)
    both = jnp.repeat(v.reshape(s, -1, 2 * e), group, axis=1)
    start = lambda_init(l)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)
    block = min(QUERY_BLOCK, s)
    pad = -s % block

    @jax.checkpoint
    def queries(args):
        q1, q2, at = args                                  # [block, pairs, e]
        keys = jnp.arange(s)[None, :]
        seen = keys <= at[:, None]
        if window is not None:
            seen = seen & (keys > at[:, None] - window)
        maps = lambda q, k: jax.nn.softmax(jnp.where(
            seen[None], jnp.einsum("qhe,khe->hqk", q, k) / math.sqrt(e),
            -jnp.inf), -1)
        return jnp.einsum("hqk,khe->qhe",
                          maps(q1, k1) - lam * maps(q2, k2), both)

    blocks = lambda t: jnp.pad(
        t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            -1, block, *t.shape[1:])
    # a padded query sits at position 0 and sees key 0: finite, dropped
    o = jax.lax.map(queries, (blocks(q1), blocks(q2), blocks(jnp.arange(s))))
    o = o.reshape(-1, pairs, 2 * e)[:s]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * p["subln"] * (1.0 - start)
    return (jnp.einsum("she,hed->sd", o.reshape(s, 2 * pairs, e),
                       p["o"]["kernel"]) + p["o"]["bias"])


def unit(x, m, p):
    return (m * jax.nn.silu(x @ p["in_proj"])) @ p["out_proj"]


def mlp(x, p):
    return ((jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"]))
            @ p["down"]["kernel"])


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


def _sequence(params, tokens, config):
    """One sequence ``tokens [s]``: its cross entropy."""
    eps = config["layer_norm_eps"]
    published = config["published"]["num_hidden_layers"]
    x = params["embedding"][tokens]
    l, memory, kv = config["first_layer"], None, None
    for i in range(_n_layers(params)):
        p = params[f"block_{i}"]
        h = layer_norm(x, p["norm"], eps)
        if "mlp" in p:                  # closes the decoder layer before it
            x = x + mlp(h, p["mlp"])
            continue
        kind = layer_kind(l, published)
        if kind == MAMBA:
            out, memory = mamba(h, p["mamba"])
        elif kind == UNIT:
            out = unit(h, memory, p["gmu"])
        elif kind == CROSS:
            out = differential_attention(h, p["cross"], l, eps, kv=kv)
        else:
            if kind == FULL:
                kv = keys_values(h, p["attn"])
            out = differential_attention(
                h, p["attn"], l, eps,
                window=config["sliding_window"] if kind == WINDOWED else None)
        x, l = x + out, l + 1
    x = layer_norm(x, params["ln_f"], eps)
    return _cross_entropy(x, params["embedding"], tokens)


def _loss(params, tokens, config):
    """``tokens [n, s]`` -> mean cross entropy."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    one = jax.checkpoint(lambda t: _sequence(params, t, config))
    return jnp.mean(jax.lax.map(one, tokens))


def loss(params, tokens, config) -> float:
    """Training loss of the batch ``tokens [n, s]``."""
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda p, t: _loss(p, t, config))(params,
                                                                tokens))


def loss_and_grad(params, tokens, config):
    """``(loss, float32 gradient)`` of the same."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t: _loss(p, t, config)))(params, tokens)


def _float32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def mixer(kind, x, p, config, l, read=None):
    """A mixer's output on its own input ``x [n, s, d]``, a sequence at a
    time: ``kind`` one of this module's five, ``l`` its published layer,
    ``read`` what it reads of an earlier layer (a unit: ``m [n, s, D]``; a
    cross layer: ``(k, v)`` ``[n, s, H_kv, e]``). A Mamba-1 layer gives
    ``(out, m)``."""
    eps = config["layer_norm_eps"]

    def run(x, read, p):
        def one(args):
            x, read = args
            if kind == MAMBA:
                return mamba(x, p)
            if kind == UNIT:
                return unit(x, read, p)
            return differential_attention(
                x, p, l, eps, kv=read if kind == CROSS else None,
                window=config["sliding_window"] if kind == WINDOWED else None)

        return jax.lax.map(one, (x, read))

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(_float32(x), _float32(read), _float32(p))


def keys_values_of(x, p):
    """The keys and values ``[n, s, H_kv, e]`` a full layer makes of its
    own input ``x [n, s, d]``: what the layers after it read."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda x, p: jax.lax.map(
            lambda one: keys_values(one, p), x))(_float32(x), _float32(p))
