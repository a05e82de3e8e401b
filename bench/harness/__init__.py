"""The benchmark's yardstick: traffic, weights, drivers, trace reduction,
operation counts and the correctness comparison."""
