"""Rounds a held expert layer ran, averaged over layers, passes and
steps: the ``gmm`` events of chip 0's window over its distinct ``gmm``
instructions and the steps. A layer that holds a share of the experts
(``models/moe.py``) runs its grouped products inside a loop of rounds of
``T`` rows whose trips the data decide, so an instruction in the loop's
body is executed once a round: 1.0 where every layer took one round in
every pass of every step, more where a layer's rows overflowed. The most
executions a step of any one instruction is printed beside it. Left out
where the program has no such call."""
from chipbench import kernel_calls

UNIT = "rounds/layer"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    found = kernel_calls.executions(trace, run, "gmm")
    if found is None:
        return None
    rounds, most, instructions = found
    print(f"moe_rounds: {rounds:.6f} executions a step of each of "
          f"{instructions} gmm instructions; most for one {most:.6f}",
          flush=True)
    return rounds
