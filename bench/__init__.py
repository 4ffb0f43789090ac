"""Chip benchmark of coded serving: ``python bench/run.py --workload <cell>``.

Everything a cell is made of is found by name: the cell's entry in
``BENCHMARK.json`` names a configuration (``configs/<name>.json`` with the
model code beside it), a traffic mix (``traffic/<name>.json``), the mix names
a fault plan (``faults/<name>.json``), and each per-layer metric is a reader
in ``metrics/<name>.py``.
"""
