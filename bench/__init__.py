"""The chip benchmark: ``python3 -m bench.run --workload <cell> ...``.

Everything that decides a number lives here and nowhere else: the
traffic generator, the window loop, the reduction from traces to
metrics, the peak table, the work each kernel call needs, and the plain
references that decide ``correct``.  From the program under test the
benchmark takes only its entry points, its counters and its kernel
names.  See ``BENCHMARK.json`` for the cells and ``PERF.md`` for what
each number means.
"""
