"""The benchmark's yardstick: traffic, load, trace reduction, FLOP and byte
counts, and the lookup of configurations, mixes and metric readers by name.
Nothing here is imported by the program under test."""
