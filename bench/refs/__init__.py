"""Plain references the benchmark compares the program with.  None of
them imports the code under test."""
