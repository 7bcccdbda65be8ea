"""The noising of block-diffusion training (Arriola et al.,
arXiv:2503.09573; the SDAR family trains by it): what a training script
calls on a batch of ids before a model built with
``GPTConfig.diffusion_block``, and hands to
``ops.losses.softmax_cross_entropy_fused`` as ``weights``.

A sequence ``x`` of ``L`` ids in blocks of ``B``: for every block ``b`` a
noise level ``t_b ~ U(eps, 1)``; every position ``i`` of it masked with
probability ``t_b``, ``m_i ~ Bernoulli(t_blk(i))``, ``x~_i = MASK if m_i
else x_i``. The model runs once on ``[x ; x~]``, and under the linear
schedule the loss is ``1 / (b L) sum_i m_i / t_blk(i) CE(logits~_i, x_i)``:
the target of position ``i`` is id ``i`` itself (no shift), and only the
masked positions count, each by ``1 / t``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def noise_blocks(key, ids, block: int, mask_id: int, eps: float = 1e-3):
    """``ids [b, L]`` -> ``(tokens [b, 2 L], targets [b, L], weights [b,
    L])``: the clean copy and then the noised one, side by side as
    ``GPT`` with ``diffusion_block=block`` takes them; the ids themselves
    as the noised rows' targets; and ``m_i / t`` a position, float32 (0
    where nothing was masked). ``t`` is drawn once a block and sequence
    from ``U(eps, 1)``; ``mask_id`` is the id a masked position shows, which
    the data's ids must not use. The same key gives the same batch."""
    b, length = ids.shape
    if block < 1 or length % block:
        raise ValueError(
            f"{length} positions are no whole blocks of {block}")
    key_t, key_m = jax.random.split(key)
    t = jnp.repeat(jax.random.uniform(
        key_t, (b, length // block), jnp.float32, eps, 1.0), block, axis=1)
    masked = jax.random.uniform(key_m, (b, length), jnp.float32) < t
    noised = jnp.where(masked, jnp.asarray(mask_id, ids.dtype), ids)
    return (jnp.concatenate([ids, noised], axis=1), ids,
            masked.astype(jnp.float32) / t)
