"""The driver's part of set-up: plan, compile or load, data, one warm step."""


def read(record, params):
    return record["setup"]["plan_compile_s"]
