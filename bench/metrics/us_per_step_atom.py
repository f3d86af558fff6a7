"""Time-to-solution: the window's wall time per MD step per atom (us)."""


def read(record):
    return record["window_s"] * 1e6 / (record["steps"] * record["atoms"])
