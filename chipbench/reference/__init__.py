"""Plain references the benchmark checks the timed path against.

Nothing here imports the program under test."""
