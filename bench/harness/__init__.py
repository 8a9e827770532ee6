"""The benchmark harness: traffic, work counts, peaks, trace reduction,
the window that drives the trainer, and the correctness comparison."""
