"""Algorithm operations per second and chip, over the steps completed."""


def read(record, params):
    steps = record["window"]["step_s"]
    if not steps:
        return None
    return (record["driver"]["ops_per_step"] * len(steps) / sum(steps)
            / record["chips"] / 1e12)
