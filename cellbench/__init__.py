"""The benchmark of ``mpi_model_tpu``: one command runs one cell
(``python3 cellbench/run.py --workload <name> ...``), found by the names
in ``BENCHMARK.json``. Configurations, traffic mixes and per-layer
metrics are files of their own under ``configs/``, ``traffic/`` and
``metrics/``; ``references/`` holds the plain references that decide
``correct``."""
