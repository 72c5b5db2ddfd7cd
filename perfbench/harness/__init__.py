"""The benchmark's yardstick: traffic generation, statistics, peaks and
FLOP counts, the plain reference, the trace reduction and the two cell
drivers. Nothing here is imported by the program under test."""
