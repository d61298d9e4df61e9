"""The plain float32 reference the benchmark's correctness checks use."""
