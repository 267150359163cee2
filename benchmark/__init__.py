"""The benchmark: BENCHMARK.json's command, its data files and its yardstick.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it (see PERF.md, "Driven by data").
"""
