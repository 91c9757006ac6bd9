"""The chip benchmark: one command runs one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that measures lives here and nowhere else: traffic generation,
weights made from the seed, the plain references that decide ``correct``,
the peak table, the operation and byte counts, the profiler-trace
reduction and one small module per metric.  The program under test is
imported from ``src/`` and only driven.
"""
