"""Plain float32 references, one file per model family.  Each holds the
published layer equations in straightforward ``jax.numpy`` with matmuls
at "highest" precision, and the generator that makes the weights from a
seed.  Nothing here imports the program."""
