"""The yardstick: clock, peaks, FLOP functions, traffic, trace reduction."""
