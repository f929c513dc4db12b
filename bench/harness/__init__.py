"""Benchmark harness for the served logic path (see ``bench/run.py``).

Everything a cell needs is found by name: the configuration file that
``BENCHMARK.json`` lists, ``bench/traffic/<traffic>.json`` and one
reader module per metric, ``bench/metrics/<metric>.py``.
"""
