"""On-chip benchmark of the RAIRS IVF-PQ search service.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator it finds.
Everything a cell needs is found by name: its deployment in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<mix>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  The corpus generator, the exact
reference, the trace reduction, the peaks table and the scan's work
count live here and import nothing of the program under test.
"""
