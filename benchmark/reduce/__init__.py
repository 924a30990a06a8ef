"""Reduction from the profiler's trace to numbers."""
