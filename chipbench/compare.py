"""The comparisons that decide ``correct``. Each returns a ``Check``:
what was compared, the value found, the bound it is held to and whether
it held. The bounds themselves are written where the check is made, each
beside its reason."""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np


@dataclasses.dataclass
class Check:
    name: str
    ok: bool
    value: object
    bound: object

    def line(self) -> str:
        return (f"check {self.name}: {'ok' if self.ok else 'FAILED'} "
                f"(value {self.value}, bound {self.bound})")


def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| over two pytrees, accumulated in float64 on the
    host, leaf by leaf."""
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x = np.asarray(jax.device_get(x), np.float64)
        y = np.asarray(jax.device_get(y), np.float64)
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum(y ** 2))
    return math.sqrt(num / den)


def close(name: str, got: float, want: float, rel_bound: float,
          floor: float = 0.0) -> Check:
    """``|got - want|`` relative to ``|want|``, or to ``floor`` where
    ``|want|`` is below it (a loss trained towards zero is compared
    absolutely)."""
    err = abs(got - want) / max(abs(want), floor)
    ok = math.isfinite(err) and err <= rel_bound
    return Check(name, ok, f"{got!r} vs {want!r}: {err:.3e} apart", rel_bound)


def trees_close(name: str, got, want, bound: float) -> Check:
    err = rel_l2(got, want)
    return Check(name, math.isfinite(err) and err <= bound,
                 f"relative L2 {err:.3e}", bound)


def holds(name: str, ok: bool, value="", bound="holds") -> Check:
    return Check(name, bool(ok), value, bound)
