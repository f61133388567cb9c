"""Step time per task of the algorithm's DAG, in microseconds."""


def read(record, params):
    steps = record["window"]["step_s"]
    tasks = record["driver"]["tasks_per_step"]
    if not steps or not tasks:
        return None
    return 1e6 * sum(steps) / (tasks * len(steps))
