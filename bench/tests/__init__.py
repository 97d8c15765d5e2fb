"""CPU tests of the benchmark's harness, generator and reductions."""
