"""Builder's tools; the benchmark's own runs use none of them."""
