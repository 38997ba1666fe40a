"""On-chip serving benchmark: one harness, driven by ``BENCHMARK.json``.

``python -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once.  Everything that belongs to one
configuration, traffic mix, per-layer metric or kernel sits in a file of
its own and is found by its name (see :mod:`chipbench.spec`).
"""
