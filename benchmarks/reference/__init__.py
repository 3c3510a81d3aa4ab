"""Plain float32 `jax.numpy` references, one module per model family.
They import nothing of the program."""
