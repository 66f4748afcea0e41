"""Files found by name (benchmark/README.md)."""
