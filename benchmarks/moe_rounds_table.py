#!/usr/bin/env python
"""moe_rounds_table.py — what the seed does to a cell whose held expert
layers run more than one round: the table of ``PERF.md`` section 6 (PR 61)
and its two prices.

    chiprun -- bash -c 'for s in 1 2 3 ...; do python3 -m chipbench.run \
        --workload mellum2-s16384 --seed $s --seconds 20 --trace 0 \
        > chiprun_out/seed_$s.log 2>&1; done'
    python benchmarks/moe_rounds_table.py chiprun_out/seed_*.log
    python benchmarks/moe_rounds_table.py --rounds-of 16384 chiprun_out/...

It reads the logs of whole runs of one cell (``chipbench.run``'s output:
the samples' median, the rows on the held experts of each expert layer at
the end of the window and the round they are worked through in, a traced
run's ``moe_rounds``), prints a line a seed, and fits a step's
milliseconds on two things that go with the seed: **the rows** on the held
experts, all layers together (the cost that follows the rows), and **the
rounds** the layers ran beyond the fewest any layer needs at these loads
(a fixed cost a round that holds few rows). Least squares over the seeds;
the residual says how much of the spread the two leave unexplained.

A builder's script: it decides nothing, and it reads no chip.
"""

import argparse
import json
import re

import numpy as np


def read(path):
    """One run's ``(seed, tokens/s/chip, rows a layer, rows a round,
    moe_rounds or None, correct)``."""
    text = open(path, errors="replace").read()
    median = float(re.search(
        r"samples of tokens/s/chip: n=\d+ min/q1/median/q3/max=\[[^,]+, "
        r"[^,]+, ([^,]+),", text).group(1))
    rows = re.search(r"rows on the experts held ([\d, ]+) in rounds of (\d+)",
                     text)
    traced = re.search(r"^moe_rounds: ([\d.]+)", text, re.M)
    result = json.loads(text.strip().splitlines()[-1])
    seed = int(re.search(r"(\d+)", path.rsplit("/", 1)[-1]).group(1))
    return (seed, median, [int(n) for n in rows.group(1).split(",")],
            int(rows.group(2)), float(traced.group(1)) if traced else None,
            result["correct"])


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("logs", nargs="+")
    p.add_argument("--rounds-of", type=int, default=None, metavar="ROWS",
                   help="count a layer's rounds in rounds of this many rows "
                        "and not of what the logs say a round holds (the "
                        "rounds that would have run)")
    p.add_argument("--tokens", type=int, default=16384,
                   help="tokens a step (a step's ms from tokens/s/chip)")
    args = p.parse_args()
    runs = sorted(read(path) for path in args.logs)
    tokens = args.rounds_of or runs[0][3]
    fewest = min(-(-n // tokens) for run in runs for n in run[2])
    table = []
    for seed, median, rows, _, traced, correct in runs:
        rounds = [-(-n // tokens) for n in rows]
        table.append((sum(rows) / 1e3, sum(r - fewest for r in rounds),
                      1e3 * args.tokens / median))
        print(json.dumps({
            "seed": seed, "tok_s_chip": median, "step_ms": table[-1][2],
            "rows_a_layer": rows, "rounds_a_layer": rounds,
            "moe_rounds_traced": traced, "correct": correct}))
    thousands, extra, ms = map(np.asarray, zip(*table))
    design = np.stack([np.ones_like(ms), thousands, extra], axis=1)
    fit, *_ = np.linalg.lstsq(design, ms, rcond=None)
    left = ms - design @ fit
    rates = 1e3 * args.tokens / ms
    print(json.dumps({
        "seeds": len(ms), "fewest_rounds_a_layer": fewest,
        "tok_s_chip_least_to_most": float(rates.max() / rates.min() - 1),
        "step_ms_at_no_rows": fit[0],
        "ms_a_step_a_thousand_rows": fit[1],
        "ms_a_step_a_round_beyond_the_fewest": fit[2],
        "residual_ms_rms": float(np.sqrt(np.mean(left ** 2))),
        "residual_ms_largest": float(np.abs(left).max()),
        "rows_thousands_least_to_most": [float(thousands.min()),
                                         float(thousands.max())],
        "rows_alone_would_spread": float(
            fit[1] * (thousands.max() - thousands.min()) / ms.mean()),
        "rounds_alone_would_spread": float(
            fit[2] * (extra.max() - extra.min()) / ms.mean())}))


if __name__ == "__main__":
    main()
