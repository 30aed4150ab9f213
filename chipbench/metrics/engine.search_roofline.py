"""Share of the HBM roofline, in %, of the searches in the traced window:
the least bytes they must move (``chipbench.work.least_bytes``) over the
chip's peak HBM bandwidth times the device's busy time in the window.

The traced window is the whole window: whole searches, and the device runs
nothing else in it, so busy time is the searches' own.  A search does about no
arithmetic per byte, so bandwidth, not FLOP/s, is its roofline.
"""


def read(record):
    t = record.get("trace")
    searches = record.get("searches")
    if not t or not searches or not t["busy_s"]:
        return None
    least = sum(s["bytes"] for s in searches)
    return 100.0 * least / (record["peak"]["hbm_bytes_per_s"] * t["busy_s"])
