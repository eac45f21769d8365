"""Plain references the benchmark compares the served answers with."""
