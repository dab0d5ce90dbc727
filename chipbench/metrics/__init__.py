"""One per-layer metric reader per file, found by the metric's name."""
