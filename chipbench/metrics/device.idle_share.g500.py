"""Share of the traced window, in %, in which no operation ran on the
device (averaged over the chips), while Graph500 searches run."""


def read(record):
    t = record.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
