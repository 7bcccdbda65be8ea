"""The selective scan of ``models/mamba.py`` (Mamba-1; Gu and Dao,
arXiv:2312.00752): a state-space recurrence whose decay is **one a channel
and state**, in plain ``jax.numpy``.

For ``u [batch, s, D]``, a step size ``delta [batch, s, D]`` (after its
softplus), ``a [D, N]`` (negative) and ``b``, ``c`` ``[batch, s, N]`` the
recurrence carries a state ``h [D, N]`` a sequence,

    h_t = exp(delta_t[:, None] a) h_{t-1} + (delta_t u_t)[:, None] b_t[None, :]
    y_t = h_t c_t                                                     ([D])

from ``h = 0`` (the skip term and the gate are the mixer's). Mamba-2's
decay is one number a head, so a chunk there is ``C B^T`` masked by
cumulative decays, a product on the MXU (``models/ssm.py``). Here the
decay ``exp(delta_t[c] a[c, n])`` differs by channel and by state: no
``[chunk, chunk]`` matrix a head exists, the chunked-product form does not
either, and the scan is elementwise work on the ``[D, N]`` state a
position: vector-unit work beside the projections' products.

``selective_scan`` is what the mixer calls. It has one body today,
``selective_scan_plain``, which holds the equations and has ``jax.grad`` of
itself for a backward pass; ``serves`` says which shapes would go to
kernels, and says none while none is built (``PERF.md`` section 7 has the
plain body's price for the ``perf_opt`` issue that brings them).

**The contract** (in the words of the delta rules', ``ops/
channel_delta_rule.py``):

- *What is float32.* The decays, their exponents, the state and the sum
  over the state are float32 (``state_dtype``, the mixer's
  ``DECAY_DTYPE``) whatever the operands come in; ``y`` is rounded to
  ``u.dtype`` once, at the end.
- *Which exponents are taken.* Only ``exp(delta_t a)`` of one position,
  ``delta >= 0`` and ``a < 0``: every factor lies in ``[0, 1]``. No
  cumulative decay is formed and none is inverted.
- *What is held.* **No array of ``[s, D, N]`` over the whole sequence,
  forward or backward.** The sequence is walked in chunks of ``chunk``
  positions with the state carried; a chunk's body is under
  ``jax.checkpoint``, so the backward pass keeps the state at each chunk's
  start alone (``s / chunk`` states of ``[D, N]`` float32: 42 MB a layer
  at 16,384 positions, 5,120 channels and a chunk of 128) and makes a
  chunk's own ``[chunk, D, N]`` again from it. Inside a chunk the walk is
  ``STEP`` positions a pass of a loop: their decays and what they add are
  made at once (``[STEP, D, N]``), and only the ``STEP`` multiply-adds on
  the state follow one another.
- *Which shapes ``serves`` sends to kernels.* None: no kernel is built.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_F32 = jnp.float32
# The chunk the scan takes where the caller names none (the longest the
# sequence allows up to this), and the positions a pass of the loop inside
# a chunk takes at once. A chunk's price is what the backward pass holds
# for it, ``[chunk, D, N]`` float32 a few times over (42 MB each at 128 x
# 5,120 x 16), against ``s / chunk`` kept states; a pass's is ``STEP`` times
# the state in flight against one loop step's fixed cost.
CHUNK, STEP = 128, 8


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the scan uses for ``seq_len`` positions."""
    return max(1, min(chunk or CHUNK, seq_len))


def serves(channels: int, state: int, chunk: int) -> bool:
    """Whether kernels take the scan at these sizes: never, while none is
    built. The mixer's counter says ``body="plain"`` by this."""
    return False


def selective_scan(u, delta, a, b, c, *, chunk: Optional[int] = None,
                   state_dtype=_F32):
    """The selective scan, chunked: ``selective_scan_plain``'s arguments
    and result, by the one body there is."""
    return selective_scan_plain(u, delta, a, b, c, chunk=chunk,
                                state_dtype=state_dtype)


def _walk(h, a, u, delta, b, c):
    """``STEP`` (or fewer) positions from the state ``h [batch, N, D]``:
    ``u``, ``delta`` ``[batch, t, D]``, ``b``, ``c`` ``[batch, t, N]`` as
    they came (cast here, a pass at a time: no float32 copy of an operand
    over the whole sequence), ``a [N, D]``. Returns the state after them
    and ``y [batch, t, D]``, both in the state's dtype."""
    u, delta, b, c = (t.astype(h.dtype) for t in (u, delta, b, c))
    decay = jnp.exp(delta[:, :, None, :] * a)           # [batch, t, N, D]
    added = (delta * u)[:, :, None, :] * b[..., None]
    states = []
    for t in range(u.shape[1]):
        h = decay[:, t] * h + added[:, t]
        states.append(h)
    y = jnp.sum(jnp.stack(states, axis=1) * c[..., None], axis=2)
    return h, y


def selective_scan_plain(u, delta, a, b, c, *, chunk: Optional[int] = None,
                         state_dtype=_F32):
    """``u [batch, s, D]``, ``delta [batch, s, D]`` (non-negative), ``a [D,
    N]`` (negative), ``b``, ``c`` ``[batch, s, N]``. Returns ``y [batch, s,
    D]`` in ``u.dtype``. A sequence the chunk does not divide is padded
    with positions whose ``delta`` is 0: they decay nothing, add nothing
    and are cut off again."""
    batch, seq, channels = u.shape
    n = a.shape[-1]
    q = chunk_for(seq, chunk)
    step = min(STEP, q)
    pad = -seq % q
    a = a.astype(state_dtype).T                         # [N, D]: D the lanes

    def chunks(t):
        """``[batch, s, w]`` as ``[chunks, batch, q, w]``."""
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        t = t.reshape(batch, (seq + pad) // q, q, t.shape[-1])
        return jnp.moveaxis(t, 1, 0)                    # [chunks, batch, q, w]

    def chunk_body(h, xs):
        # a chunk that ``step`` does not divide ends in one shorter pass
        u_c, delta_c, b_c, c_c = xs                     # [batch, q, w]
        whole = q - q % step

        def passes(t):
            return jnp.moveaxis(t[:, :whole].reshape(
                batch, whole // step, step, t.shape[-1]), 1, 0)

        h, y = jax.lax.scan(
            lambda h, x: _walk(h, a, *x), h,
            (passes(u_c), passes(delta_c), passes(b_c), passes(c_c)))
        y = jnp.moveaxis(y, 0, 1).reshape(batch, whole, channels)
        if whole < q:
            h, rest = _walk(h, a, u_c[:, whole:], delta_c[:, whole:],
                            b_c[:, whole:], c_c[:, whole:])
            y = jnp.concatenate([y, rest], axis=1)
        return h, y

    # the backward pass keeps a chunk's entering state and makes the rest
    # of the chunk again
    init = jnp.zeros((batch, n, channels), state_dtype)
    _, y = jax.lax.scan(jax.checkpoint(chunk_body), init,
                        (chunks(u), chunks(delta), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(batch, seq + pad, channels)[:, :seq]
    return y.astype(u.dtype)


def selective_scan_by_position(u, delta, a, b, c):
    """The recurrence a position at a time, float32: what the chunked body
    is held to in the tests."""
    a = a.astype(_F32)

    def one(h, x):
        u_t, delta_t, b_t, c_t = x                      # [batch, D] / [batch, N]
        h = (jnp.exp(delta_t[..., None] * a) * h
             + (delta_t * u_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    by_position = lambda t: jnp.moveaxis(t.astype(_F32), 1, 0)
    init = jnp.zeros((u.shape[0], u.shape[2], a.shape[-1]), _F32)
    _, y = jax.lax.scan(one, init, tuple(map(by_position, (u, delta, b, c))))
    return jnp.moveaxis(y, 0, 1)
