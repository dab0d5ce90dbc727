"""The benchmark's yardstick: cell discovery, traffic, instrumentation,
trace reduction, work counts, peaks and the correctness comparison."""
