"""The benchmark's yardstick: traffic, statistics, peaks, FLOP/byte counts,
trace reduction, the serving driver and the correctness check."""
