"""The chip benchmark of the serving path.

``bench/run.py`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line.  Everything the benchmark measures with lives here:
the traffic generator, the reduction from stamps, counters and the
profiler trace to metrics, the table of peaks, the FLOP and byte counts,
the plain float32 reference and the comparison that decides
``correct``.  From the program it takes only the system under test
(``FleetSpec`` -> ``ServingClient``), its stage events and counters, and
its kernel names.
"""
