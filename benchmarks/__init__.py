"""The benchmark's own files: harness, yardstick and data (see README.md)."""
