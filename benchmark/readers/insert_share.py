"""Share of the step time the host spent inserting tasks."""


def read(record, params):
    insert = record["window"]["span_s"].get("insert")
    steps = record["window"]["step_s"]
    if insert is None or not steps:
        return None
    return 100.0 * insert / sum(steps)
