"""The cell benchmark of vllm-omni-tpu (see BENCHMARK.json and PERF.md).

Everything the yardstick needs lives here: traffic generation, the
reduction from client records, counters and device traces to metrics,
the table of peaks and, one module for each model family under ``archs/``,
the plain reference with the counts of operations and bytes.  From the
program it takes only the system under test and its counters and kernel
names.
"""
