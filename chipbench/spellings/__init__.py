"""Spellings: the ways the package offers to write a training step over
the cell's chips. A cell file names one under ``"spelling"``; the harness
imports ``chipbench.spellings.<name>`` and calls
``build(job, devices) -> Spelled``. A later PR that measures another way
of dividing the work (FSDP, tensor or sequence parallel) adds a module
here and edits nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import compare


@dataclasses.dataclass
class Spelled:
    """``state_sharding`` / ``batch_sharding``: where the harness places
    parameters, model state and optimizer state, and the batch.
    ``tx``: the optimizer as the step uses it (``hvt.DistributedOptimizer``
      around the family's).
    ``step(params, extra, opt_state, batch) -> (params, extra, opt_state,
      loss)``: one training step, not yet jitted; the harness jits it
      with the three state arguments donated.
    ``verify_before(key) -> [Check]``: what can be checked on the probe
      job before the optimizer state exists.
    ``verify_compiled(compiled) -> [Check]``: on the compiled step (a
      ``jax.stages.Compiled``).
    ``verify_after(params) -> [Check]``: on the parameters after the
      window.
    """

    state_sharding: Any
    batch_sharding: Any
    tx: Any
    step: Callable
    verify_before: Callable[[Any], list]
    verify_compiled: Callable[[Any], list]
    verify_after: Callable[[Any], list]


def has_all_reduce(compiled) -> compare.Check:
    return compare.holds("all_reduce_in_compiled_step",
                         "all-reduce" in compiled.as_text(), "", "present")


def replicas_identical(tree, mesh, axis: str) -> compare.Check:
    """Every leaf of a replicated tree holds the same bits on every chip
    of ``mesh``: per leaf, the all-reduced maximum and minimum of the
    bit patterns agree everywhere. Exact, and nothing leaves the device
    but one count a leaf."""
    stacked_sharding = NamedSharding(mesh, P(axis))
    n = mesh.devices.size

    def mismatches(x):              # x: this chip's copy, [1, ...]
        bits = jax.lax.bitcast_convert_type(
            x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
        differ = jax.lax.pmax(bits, axis) != jax.lax.pmin(bits, axis)
        return jnp.sum(differ, dtype=jnp.int32)[None]

    count = jax.jit(jax.shard_map(mismatches, mesh=mesh, in_specs=P(axis),
                                  out_specs=P(axis)))
    counts = []
    for leaf in jax.tree.leaves(tree):
        shards = sorted(leaf.addressable_shards,
                        key=lambda s: list(mesh.devices.flat).index(s.device))
        stacked = jax.make_array_from_single_device_arrays(
            (n, *leaf.shape), stacked_sharding,
            [s.data[None] for s in shards])
        counts.append(count(stacked))
    total = int(sum(int(np.sum(jax.device_get(c))) for c in counts))
    return compare.holds(f"parameters_bit_identical_on_{n}_chips",
                         total == 0, f"{total} elements differ", 0)
