"""The benchmark of tpu-search's served path: `BENCHMARK.json` at the
root of the repo names the cells; `python3 -m benchmark` runs one."""
