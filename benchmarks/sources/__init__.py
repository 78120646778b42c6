"""Per-layer metric sources: one module a kind, found by the name in a metric file."""
