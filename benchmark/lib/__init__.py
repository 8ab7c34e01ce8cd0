"""The yardstick: generator, trace reduction, peaks and work counts.

Nothing here imports the program under test."""
