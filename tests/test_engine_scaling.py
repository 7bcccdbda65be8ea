"""Regression pin for the eager data-plane scaling work
(benchmarks/engine_scaling.py, docs/performance.md): the shm plane must
not lose to the loopback TCP ring at the 16 MB payload where its
single-copy design wins by design.

Timing on a shared 1-core box is noisy, so the comparison interleaves
shm/ring pairs and compares MEDIANS with headroom — a real regression
(shm slower than ring by design, as a naive barrier bug would cause)
clears the margin; scheduler noise does not.
"""

import importlib.util
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "engine_scaling", os.path.join(REPO, "benchmarks", "engine_scaling.py"))
engine_scaling = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(engine_scaling)


@pytest.mark.slow  # a ratio of timings on a CPU the rest of tier-1 shares is
#                    not a speed; the full `./ci.sh` run keeps it
@pytest.mark.timeout(1500)
def test_shm_not_slower_than_ring_at_16mb_and_64mb_2proc():
    """No retry loop (round-4): the historical flake source was the shm
    barrier's FIXED 50µs nap stealing quanta from the working rank on
    the oversubscribed 1-core box — worst exactly at big payloads (the
    round-3 '64 MB cliff': shm 1024 ms vs ring 391 ms hit). With
    exponential backoff (backends.cc Barrier) five interleaved rounds
    measured shm >= ring at BOTH sizes (medians 39.7 vs 42.3 ms at
    16 MB, 370.7 vs 391.2 ms at 64 MB), so the pin now covers both and
    a single interleaved-median round suffices."""
    sizes = {"16MB": 1 << 22, "64MB": 1 << 24}
    shm_ms = {k: [] for k in sizes}
    ring_ms = {k: [] for k in sizes}
    for _ in range(3):  # interleaved pairs: noise hits both alike
        r_shm = engine_scaling.run_job(2, True, sizes, 4, REPO)
        r_ring = engine_scaling.run_job(2, False, sizes, 4, REPO)
        for k in sizes:
            shm_ms[k].append(r_shm[k]["hit_ms"])
            ring_ms[k].append(r_ring[k]["hit_ms"])
    for k in sizes:
        shm = float(np.median(shm_ms[k]))
        ring = float(np.median(ring_ms[k]))
        assert shm <= ring * 1.25, (
            f"shm {k} allreduce lost to loopback TCP (median {shm:.1f} "
            f"vs {ring:.1f} ms; raw {shm_ms[k]} vs {ring_ms[k]}) — the "
            f"single-copy shm plane should not lose")
