"""The dmlc-tpu benchmark: the yardstick, kept apart from the program.

See benchmarks/README.md.  Nothing here is imported by dmlc_tpu, and
from dmlc_tpu the benchmark takes only the system under test and its
spans, counters and kernel names.
"""
