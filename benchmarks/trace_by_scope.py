#!/usr/bin/env python
"""trace_by_scope.py — a traced benchmark run split by named scope and by
pass, with the operations that take most of each scope.

    python3 -m chipbench.run --workload kanana2-s8192 --seed 1 --trace 1
    python benchmarks/trace_by_scope.py --scopes mla_,moe_,dense_mlp \
        --out chiprun_out/by_scope.json

Reads the last traced run of this checkout (``.chipbench_trace``, or the
``.xplane.pb`` given) with ``chipbench/xplane.py`` and the program's own
names (``chipbench/regions.py``): for every ``jax.named_scope`` whose
name starts with one of ``--scopes``, the ms a step of chip 0's operations
whose naming part holds ``/<scope>/``, forward / recompute / backward, and
the ``--top`` labels (``Op.label`` with XLA's name for a fusion) with most
of it, and how often a step each Pallas kernel runs, by name
(``kernel_calls_a_step``, whatever its scope; counted by
``chipbench/kernel_calls.py``, as the per-layer metrics that read a
kernel's calls count them). ``while`` events are left out: a loop's event
spans its body's.
What section 5 of PERF.md gives "by scope and pass" is this script's.

A builder's script: it decides nothing, and reads no chip.
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.getcwd())


def by_scope(path, prefixes, top):
    from chipbench import kernel_calls, regions, xplane

    trace, names = xplane.load(path), regions.name_stacks(path)
    scopes, labels, loops = {}, {}, 0.0
    # chip 0's operations inside its window, ms a step, collectives out:
    # what ``regions.region_ms`` sums
    for op, ms in regions._ops_ms(trace):
        if op.label.startswith("while"):
            loops += ms
            continue
        part, region = regions.naming_part(names.get(op.name, ""))
        # %maximum_bitcast_fusion.3 = ... -> maximum_bitcast_fusion
        what = re.sub(r"[.\d]+$", "", op.name.lstrip("%"))
        for scope in re.findall(r"/(\w+)(?=/)", part):
            if scope.startswith(prefixes):
                split = scopes.setdefault(scope, {})
                split[region] = split.get(region, 0.0) + ms
                found = labels.setdefault(scope, {})
                found[what] = found.get(what, 0.0) + ms
                break
    for split in scopes.values():
        split["all"] = sum(split.values())
    walked = kernel_calls.walk(trace, names)
    return {"trace": path, "steps": walked.steps, "while_events_ms": loops,
            "kernel_calls_a_step": kernel_calls.calls_a_step(walked),
            "by_scope": scopes,
            "top": {s: sorted(map(list, found.items()),
                              key=lambda kv: -kv[1])[:top]
                    for s, found in labels.items()}}


def main(argv=None):
    from chipbench import regions

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", default=None)
    ap.add_argument("--scopes", default="mla_,moe_,dense_mlp,lm_head,embed")
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    path = a.trace or regions.trace_file(os.path.join(os.getcwd(),
                                                      ".chipbench_trace"))
    if path is None:
        sys.exit("trace_by_scope: no traced run under .chipbench_trace")
    out = json.dumps(by_scope(path, tuple(a.scopes.split(",")), a.top))
    print(out)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            f.write(out + "\n")


if __name__ == "__main__":
    main()
