"""The on-chip benchmark of paddle_tpu: yardstick code, data and readers.

Entry point: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  ``BENCHMARK.json`` at the repo root is the
index; every configuration, traffic mix and per-layer metric is a file of its
own found by name (see ``harness.py``)."""
