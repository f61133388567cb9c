"""A counter of the program over the window, per task of the window's
steps: ``params["counter"]`` names it.

The driver leaves the chip modules' counters, first reading taken from
the last, in the dict its ``setup()`` returned as ``program_counters``
(``record["setup"]`` holds that dict). ``None`` where there is nothing to
read: a program without the counter, a driver that leaves none, no step.
"""


def read(record, params):
    counts = record["setup"].get("program_counters") or {}
    steps = record["window"]["attempted"] - record["window"]["failed"]
    tasks = record["driver"]["tasks_per_step"] * steps
    if params["counter"] not in counts or tasks <= 0:
        return None
    return counts[params["counter"]] / tasks
