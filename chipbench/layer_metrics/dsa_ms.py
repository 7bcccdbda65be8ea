"""Time chip 0 spends a step in the sparse-attention mixers
(``horovod_tpu/models/dsa.py``): every operation under one of its six
scopes ``dsa_proj``, ``dsa_index``, ``dsa_select``, ``dsa_core``,
``dsa_target`` and ``dsa_out_proj``, forward, recomputed and backward
together, so it overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by
construction. A ``while`` event is left out as
``benchmarks/trace_by_scope.py`` leaves it out (a loop's event spans its
body's). Left out where the program has no such scope."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

INDEX, SELECT, CORE, TARGET = ("/dsa_index/", "/dsa_select/", "/dsa_core/",
                               "/dsa_target/")
SCOPES = ("/dsa_proj/", INDEX, SELECT, CORE, TARGET, "/dsa_out_proj/")


def under(trace, scopes, kernels_only=False):
    """ms a step of chip 0's operations whose name stack holds one of
    ``scopes`` (of its Pallas calls alone, ``kernels_only``); None where
    there is no trace, no program in it, no window or no such
    operation."""
    path = regions.trace_file() if trace is not None else None
    names = regions.name_stacks(path) if path else None
    ops = regions._ops_ms(trace) if names else None
    if ops is None:
        return None
    return sum(ms for op, ms in ops
               if not op.label.startswith("while")
               and (op.kind == "kernel" or not kernels_only)
               and any(s in regions.naming_part(names.get(op.name, ""))[0]
                       for s in scopes)) or None


def read(trace, run):
    return under(trace, SCOPES)
