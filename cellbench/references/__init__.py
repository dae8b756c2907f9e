"""Plain references: straightforward ``jax.numpy`` at float32 ``highest``,
importing nothing of the program and taking nothing the program has made."""
