"""On-chip benchmark of the evaluator: one cell per run, found by name from
``BENCHMARK.json`` (see ``chipbench/harness.py``)."""
