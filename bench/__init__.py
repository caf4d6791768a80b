"""The on-chip benchmark of the GA system: harness, traffic generator,
plain reference, trace reduction, work and peak tables.  `bench/run.py`
runs one cell; `BENCHMARK.json` names the cells."""
