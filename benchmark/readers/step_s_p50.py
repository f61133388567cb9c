"""Median step time."""

from benchmark import stats


def read(record, params):
    steps = record["window"]["step_s"]
    return stats.median(steps) if steps else None
