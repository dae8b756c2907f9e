"""Found by name; see cellbench/README.md."""
