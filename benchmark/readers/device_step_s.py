"""Device-busy seconds per traced step, mean over chips."""


def read(record, params):
    return record["trace"].get("device_step_s")
