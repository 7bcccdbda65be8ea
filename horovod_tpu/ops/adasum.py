"""Adasum — scale-invariant gradient combination.

Math (reference ``horovod/common/ops/adasum/adasum.h:338-420``): for two
gradients a, b,

    adasum(a, b) = (1 - a·b / (2‖a‖²)) a  +  (1 - a·b / (2‖b‖²)) b

applied recursively over a binary tree of ranks (vector-halving
distance-doubling in the reference, ``adasum.h:194-336``; power-of-two world
size required, enforced at ``tensorflow/__init__.py:146-147``).

TPU-native design: each recursion level pairs ranks with stride 2^k and runs
ONE pairwise ``psum`` (via ``axis_index_groups``) to give both members
s = a + b; from s each member reconstructs its partner's vector locally
(partner = s − mine), so a·b, ‖a‖², ‖b‖² and the combine are all local math
— no point-to-point sends, no extra scalar collectives. log2(n) small-group
psums replace VHDD's halved-vector MPI exchanges; XLA schedules them on ICI.
Dot/norm accumulation is fp32 regardless of input dtype, like the
reference's ``DispatchComputeDotAndNormSqrds`` (``adasum.h:434-466``).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def pairwise_adasum(a, b):
    """The scalar-coefficient pairwise combine, fp32 accumulation.

    Guards the zero-norm cases like the reference (``adasum.h:372-383``).
    Exposed for tests and for the eager/C++ path to cross-check against.
    """
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    dot = jnp.sum(af * bf)
    a_sq = jnp.sum(af * af)
    b_sq = jnp.sum(bf * bf)
    ca = jnp.where(a_sq > 0, 1.0 - dot / (2.0 * a_sq), 1.0)
    cb = jnp.where(b_sq > 0, 1.0 - dot / (2.0 * b_sq), 1.0)
    return (ca * af + cb * bf).astype(a.dtype)


def adasum_reduce(t, axis_name, axis_index_groups=None, start_level=None):
    """Adasum-combine ``t`` across the mesh axis (traced path).

    At level k, ranks pair with stride 2^k inside blocks of 2^(k+1); after
    log2(n) levels every rank holds adasum over all ranks, matching the
    reference's recursion order (``adasum.h:194-336``).

    ``axis_index_groups``: optional partition of the axis (a process set
    plus its complement, or any partition). Every group of size >= 2 must
    be power-of-two sized and is adasum-combined internally; singleton and
    complement members pass through unchanged (the reference's
    "not included" semantics).

    ``start_level``: levels with stride < start_level use a plain AVERAGE
    instead of the adasum combine — the reference's GPU start_level trick
    (``adasum.h:177-183``: intra-node levels average, only cross-node
    levels run the scale-invariant combine; the GPU op passes local_size).
    Default 1 (pure adasum); the ``HVT_ADASUM_START_LEVEL`` env var sets a
    global default (an integer, or ``local`` for the local mesh size).
    The pairing is by axis-index adjacency, so ``local`` assumes the mesh
    axis orders same-host chips contiguously (the default host-major
    ordering of ``global_mesh``).
    """
    n = lax.axis_size(axis_name)
    if start_level is None:
        import os

        raw = os.environ.get("HVT_ADASUM_START_LEVEL", "1")
        if raw == "local":
            from horovod_tpu.common import basics

            start_level = basics.local_size()
        else:
            start_level = int(raw)
    start_level = max(1, int(start_level))

    if axis_index_groups is None:
        member_groups = [list(range(n))]
    else:
        member_groups = [list(g) for g in axis_index_groups]
    for g in member_groups:
        if len(g) & (len(g) - 1):
            raise ValueError(
                f"Adasum requires power-of-two group sizes, got {len(g)} "
                "(reference enforces the same: tensorflow/__init__.py:146)")
    max_size = max(len(g) for g in member_groups)
    if max_size == 1:
        return t

    orig_dtype = t.dtype
    v = t.astype(jnp.float32)

    from horovod_tpu.ops.collective_ops import Sum, grouped_reduce

    levels = int(max_size).bit_length() - 1
    for k in range(levels):
        stride = 1 << k
        block = stride << 1
        pair_groups = []
        paired = []
        for g in member_groups:
            if stride < len(g):
                for base in range(0, len(g), block):
                    for off in range(stride):
                        pair_groups.append(
                            [g[base + off], g[base + off + stride]])
                paired.extend(g)
            else:
                # finished groups / complement: singleton no-op reduces
                # keep the partition covering the whole axis
                pair_groups.extend([r] for r in g)

        s = grouped_reduce(v, Sum, axis_name, pair_groups)  # a + b
        if stride < start_level:
            # below start_level: plain average of the pair; members whose
            # group is done (singletons) must keep their value
            half = 0.5 * s
            if len(paired) == n:
                v = half
            else:
                idx = lax.axis_index(axis_name)
                mask = jnp.isin(idx, jnp.asarray(paired))
                v = jnp.where(mask, half, v)
            continue
        partner = s - v  # singletons: partner = 0 → combine is identity
        my_sq = jnp.sum(v * v)
        partner_sq = jnp.sum(partner * partner)
        dot = jnp.sum(v * partner)

        # The pairwise combine is symmetric in (a, b), so both members
        # compute the identical result with their own/partner roles.
        cv = jnp.where(my_sq > 0, 1.0 - dot / (2.0 * my_sq), 1.0)
        cp = jnp.where(partner_sq > 0, 1.0 - dot / (2.0 * partner_sq), 1.0)
        v = cv * v + cp * partner

    return v.astype(orig_dtype)
