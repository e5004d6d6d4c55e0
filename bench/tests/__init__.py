"""CPU tests of the benchmark harness; they load no TPU library."""
