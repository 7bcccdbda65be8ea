"""Process start to the first measured step: reaching the chip, making
weights and batch, compiling or reading the cache, warm-up."""
UNIT = "s"


def read(trace, run):
    return run["setup_seconds"]
