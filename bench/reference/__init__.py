"""Plain references of the benchmarked models, one module per family.

Each is a straightforward ``jax.numpy`` float32 implementation of the
published layer equations at full matmul precision, written from the
configuration file's sizes.  None imports the program (``repro``) or
takes anything it made: the weights come from :mod:`bench.weights`.
"""
