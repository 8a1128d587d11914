"""The benchmark's own library: traffic generation, window accounting,
the pack cache, seeded weights, the plain float32 reference, operation and
byte counts, and the reduction from profiler traces to metrics.

Nothing here names a cell, a configuration or a traffic mix: those are
data files under ``bench/``, found by the names in ``BENCHMARK.json``.
"""
