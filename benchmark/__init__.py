"""The benchmark of the served shuffle path (BENCHMARK.json names the cells).

Everything that decides a number lives in this directory, where a later PR
that changes the program cannot change it: record generation, the plain
reference and the comparison, the job loop and its clocks, the reduction from
spans and the profiler's trace to metrics, the peaks table.  From the program
it takes only the system under test and its spans, counters and kernel names.
"""
