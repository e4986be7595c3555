"""The cell benchmark of vllm-omni-tpu (see BENCHMARK.json and PERF.md).

Everything the yardstick needs lives here: traffic generation, the
reduction from client records, counters and device traces to metrics,
the table of peaks, the plain reference.  From the program it takes only
the system under test and its counters and kernel names.
"""
