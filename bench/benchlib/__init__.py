"""The on-chip benchmark's shared pieces: what ``bench/run.py`` drives.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``; this package is the general code
that finds those files by the names in ``BENCHMARK.json`` and runs them.
"""
