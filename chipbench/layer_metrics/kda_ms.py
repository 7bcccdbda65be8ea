"""Time chip 0 spends a step in the Kimi Delta Attention mixers
(``horovod_tpu/models/kda.py``): every operation under one of its five
scopes ``kda_in_proj``, ``kda_conv``, ``kda_rule``, ``kda_gate_norm`` and
``kda_out_proj``, forward, recomputed and backward together, so it
overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by construction.
``while`` events are left out, as ``benchmarks/trace_by_scope.py`` leaves
them out: a loop's event spans its body's, and the rule's plain body holds
two loops (the passes of heads, the carry over the chunks). Left out where
the program has no such scope."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

PASSES = ("forward", "recompute", "backward")
RULE = "/kda_rule/"
SCOPES = ("/kda_in_proj/", "/kda_conv/", RULE, "/kda_gate_norm/",
          "/kda_out_proj/")


def under(trace, scopes):
    """ms a step of chip 0's operations, kernels and XLA's alike and
    containers left out, whose naming part holds one of ``scopes`` in one
    of the three passes; None without a trace, a program in it or a
    window, and where nothing is under them."""
    path = regions.trace_file() if trace is not None else None
    names = regions.name_stacks(path) if path else None
    ops = regions._ops_ms(trace) if names else None
    if ops is None:
        return None
    total = 0.0
    for op, ms in ops:
        part, found = regions.naming_part(names.get(op.name, ""))
        if (found in PASSES and not op.label.startswith("while")
                and any(scope in part for scope in scopes)):
            total += ms
    return total or None


def read(trace, run):
    return under(trace, SCOPES)
