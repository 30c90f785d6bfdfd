"""On-chip serving benchmark: one harness, driven by ``BENCHMARK.json``.

A cell names a configuration file (``bench/configs/<name>.json``) and a
traffic file (``bench/traffic/<name>.json``); a per-layer metric is a
reader ``bench/metrics/<name>.py``.  ``bench/run.py`` finds all three by
name, so a cell, a configuration or a metric is added by adding files
and entries only.
"""
