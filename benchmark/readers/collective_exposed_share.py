"""Share of the traced window spent in collectives with nothing beside."""


def read(record, params):
    share = record["trace"].get("collective_exposed_share")
    return None if share is None else 100.0 * share
