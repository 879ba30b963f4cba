"""The benchmark's harness: traffic, drive loop, counts, trace reduction
and the check that decides ``correct``.  Entry points: ``bench/run.py``
(one run of one cell), ``bench/readings.py`` and ``bench/sweep.py``."""
