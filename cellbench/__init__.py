"""cellbench — the cell benchmark of libskylark_tpu (see README.md).

One command runs one cell (a configuration under a traffic mix) once:

    python3 -m cellbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, driver, loop,
reference, count function or per-layer metric is a file of its own that the
harness finds by the name in ``BENCHMARK.json``; the harness itself names no
workload.
"""
