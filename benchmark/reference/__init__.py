"""Plain references the benchmark compares the timed path with."""
