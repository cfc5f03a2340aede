"""The benchmark's yardstick: load generation, statistics, the trace
reduction, peaks, operation and byte counts, seeded weights, the two
runner kinds and the comparison that decides ``correct``.

Nothing here is imported by the program; later PRs may add files beside
these and may not edit them (see ``PERF.md``).
"""
