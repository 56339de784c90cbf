"""One file per per-layer metric: ``META`` and ``read(facts)``
(benchmark/lib/layers.py)."""
