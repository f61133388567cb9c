"""Share of the traced window in which no operation ran on the chip."""


def read(record, params):
    idle = record["trace"].get("idle_share")
    return None if idle is None else 100.0 * idle
