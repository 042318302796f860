"""Plain references of the configurations' mathematics, in straightforward
`jax.numpy` at float32 with every matrix product at `highest` precision.
They import nothing of the program and take nothing it made: the weights
they read are the benchmark's own (bench/weights.py). `Numerics(inputs)`
computes the same mathematics with every matrix-product input rounded to
a lower precision first: the configuration's stated one, or the one
below it (the control)."""
