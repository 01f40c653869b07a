"""Plain references: straightforward ``jax.numpy`` in float32 at
``precision=highest``, no kernels, no cache, no batching tricks.  Nothing here
imports the program; the weights come from ``perfbench.weights``."""
