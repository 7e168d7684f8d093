"""The benchmark's own code: traffic, serving loop, trace reduction,
operation counts and the correctness comparison.  Nothing here is imported
by the program under test."""
