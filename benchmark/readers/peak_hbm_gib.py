"""Peak device memory on the fullest chip, as the allocator reports it."""


def read(record, params):
    return record["peak_bytes"] / 2 ** 30 if record["peak_bytes"] else None
