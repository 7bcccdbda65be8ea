"""Tokens per second per chip: the median sample of the window."""
UNIT = "tokens/s/chip"


def read(trace, run):
    return run["items_per_s_chip"] if run["item"] == "tokens" else None
