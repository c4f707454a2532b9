"""On-chip serving benchmark: cells, traffic, metric readers and the plain
reference that decides ``correct``.  ``python3 chipbench/run.py --help``."""
