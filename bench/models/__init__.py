"""Weights and plain references, one module per model family."""
