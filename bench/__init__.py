"""The chip benchmark: cells named in ``BENCHMARK.json``, each one model
configuration (``configs/``) under one traffic mix (``traffic/``), with one
reader per per-layer metric (``metrics/``).  ``python bench/run.py --help``
says how to run a cell."""
