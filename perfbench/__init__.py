"""The benchmark: one command runs one cell once (see perfbench/README.md)."""
