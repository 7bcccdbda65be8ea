"""Device memory the compiled train step needs on one chip, in GiB:
arguments + outputs + temporaries - aliased, from the executable's
``memory_analysis()``. It repeats exactly and decides whether a job
fits."""
UNIT = "GiB"


def read(trace, run):
    if run["step_bytes"] is None:
        return None
    return run["step_bytes"] / 2 ** 30
