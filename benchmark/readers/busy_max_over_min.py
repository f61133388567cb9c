"""Busiest chip's busy seconds over the least busy chip's."""


def read(record, params):
    return record["trace"].get("busy_max_over_min")
