"""Plain references, one module per model family: weights from the
seed in the program's parameter layout, the loss in straightforward
``jax.numpy`` at float32, and AdamW.  Matmuls run at the precision
the configuration states (``matmul_precision``), which the caller sets
around the whole reference (``jax.default_matmul_precision``).
They import nothing of the program."""
