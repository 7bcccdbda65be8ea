"""``memory_stats()["peak_bytes_reserved"]`` after the window and before
any reference program runs, the largest over the cell's chips, in GiB.
On libtpu 0.0.34 it is what the runtime set aside for programs'
temporaries; live arrays are counted apart, as "in use"."""
UNIT = "GiB"
LAYER = "device"
MOVES = "hbm_step"


def read(trace, run):
    peaks = [m["peak_bytes_reserved"] for m in run["memory_stats"]
             if "peak_bytes_reserved" in m]
    return max(peaks) / 2 ** 30 if peaks else None
