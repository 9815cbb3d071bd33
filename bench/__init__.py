"""The chip benchmark of the graph store: one command, data-driven cells.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` on the TPU it is started on. Everything
that defines a cell lives in files found by name: `configs/<config>.json`
(the deployment), `traffic/<mix>.json` (the mix's parameters, read by the
driver it names under `drivers/`), and `metrics/<metric>.py` (one reader
per per-layer metric).
"""
