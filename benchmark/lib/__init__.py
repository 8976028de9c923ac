"""The yardstick: traffic generation, drivers, trace reduction, peaks, FLOP
and byte counts, the plain reference and the comparison that decides
``correct``. Nothing here is imported by the program under test."""
