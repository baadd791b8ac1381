"""The benchmark's yardstick: traffic generation, the timed loop, the
comparison that decides ``correct``, the reduction from trace to metrics,
the peaks table.  Takes only the system under test from the program."""
