"""Mean wait of the window's queries, in ms, from when each was due until
the ``flush`` that served it started."""
import numpy as np


def read(record):
    wait = record.get("queue_wait_s")
    if wait is None or not len(wait):
        return None
    return float(np.mean(wait) * 1e3)
