"""On-chip benchmark harness (see bench/README.md)."""
