"""The benchmark's yardstick: peaks, FLOP counts, trace reduction, load
generation, plain references. Nothing here imports the program."""
