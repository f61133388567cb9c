"""The yardstick: parsec_tpu's benchmark, driven by the data files beside it.

`python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once. See `benchmark/README.md`.
"""
