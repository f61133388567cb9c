"""XLA backend compiles inside the measured window (want 0)."""


def read(record, params):
    return record["window"]["compiles"]
