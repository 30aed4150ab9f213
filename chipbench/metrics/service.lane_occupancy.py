"""Share of the batch lanes filled, in %: the window's change in
``ServiceStats.lanes_used`` over its change in ``batches`` times the lane
budget."""


def read(record):
    st = record.get("stats")
    if not st or not st["batches"]:
        return None
    return 100.0 * st["lanes_used"] / (st["batches"] * record["budget"])
