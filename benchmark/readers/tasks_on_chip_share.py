"""Share of the window's tasks that ran on a chip's device module."""


def read(record, params):
    tasks = record["window"]["tasks_by_module"]
    if not tasks or not sum(tasks.values()):
        return None
    on_chip = sum(n for name, n in tasks.items() if name.startswith("tpu"))
    return 100.0 * on_chip / sum(tasks.values())
