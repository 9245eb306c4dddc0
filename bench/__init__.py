"""On-chip benchmark of the frequency-aware embedding cache.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip and prints one JSON result
line.  Everything a cell needs is found by name: ``configs/<config>.json``
(sizes, the family whose adapter and plain reference run it, correctness
limits), ``traffic/<mix>.json`` (parameters of ``generator.py``) and
``metrics/<metric>.py`` (one reader per per-layer metric).
"""
