"""The benchmark: one served harness driven by data (see README.md)."""
