"""Plain references: straightforward jax.numpy in float32 at the
'highest' matmul precision, written from the published descriptions.
They import nothing of the program under test."""
