"""Traffic kinds: one module a kind, found by the name in a traffic file."""
