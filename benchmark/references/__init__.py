"""Plain references: the architectures' equations in straightforward
float32 ``jax.numpy``.  They import nothing of the program."""
