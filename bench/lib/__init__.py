"""Shared pieces of the benchmark: spec lookup, seeds, peaks, trace
reduction and the correctness comparison."""
