"""The chip benchmark: BENCHMARK.json's cells, run one per process.

`python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` drives one cell through the node's BLS entry points and
prints one JSON result line. Everything a cell needs is data found by
name: its configuration under `configs/`, its traffic mix under
`traffic/`, and a reader per per-layer metric under `metrics/`.
"""
