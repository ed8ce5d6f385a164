"""Operation and byte counts of the benchmark's kernels, from shapes alone."""
