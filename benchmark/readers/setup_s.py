"""From the TPU backend being up to the first timed step."""


def read(record, params):
    return record["setup"]["setup_s"]
