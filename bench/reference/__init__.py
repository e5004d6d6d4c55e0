"""Plain references: each architecture's forward in float32 ``jax.numpy``.

They import nothing of the program and take nothing it made.  Each
draws its own weights from the seed with the same recipe the program's
loader follows (truncated normal at ``1/sqrt(fan_in)``, rounded to the
bf16 the configuration serves in), keeps them in float32 and computes at
``highest`` matmul precision, with no quantization, cache or batching.
``bits=4`` computes in int4 where the program computes in int8: every
weight the plan covers (per output channel), the input rows of those
matmuls and the cached K/V.  That is the lower-precision control.
"""
