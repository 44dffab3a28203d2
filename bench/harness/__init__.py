"""The benchmark's harness: cells, traffic, the driven engine, readings."""
