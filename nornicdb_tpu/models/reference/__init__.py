"""Plain references: each architecture's published forward pass in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision, with no
kernel, cache or batching.  What the served paths are held to."""
