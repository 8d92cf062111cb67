"""Plain float32 references the benchmark compares the program with.
They import nothing of the program and take none of its results."""
