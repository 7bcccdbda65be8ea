"""Images per second per chip: the median sample of the window."""
UNIT = "images/s/chip"


def read(trace, run):
    return run["items_per_s_chip"] if run["item"] == "images" else None
