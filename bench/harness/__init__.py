"""The benchmark's yardstick: spec loading, traffic, driver loop, latency
and rate arithmetic, trace reduction, FLOP/byte counters and the
correctness comparison. Nothing here imports the program's metric code."""
