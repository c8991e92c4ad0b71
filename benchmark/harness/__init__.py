"""The benchmark's yardstick: loader, traffic generator, timing, trace
reduction, peaks, required work and the plain reference. Nothing here names a
cell, a configuration, a traffic mix or a per-layer metric: those are files
found by the names in BENCHMARK.json."""
