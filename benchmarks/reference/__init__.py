"""Plain references: straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``, written from the papers.  No
kernels, no cache, no batching tricks, and nothing imported from
``deepspeed_tpu``.  They read the same parameter tree the system under
test is given (``benchmarks/models``), made by the benchmark from the seed.

Departures from the papers, each also a property of the configurations:
GELU is the tanh form (the original BERT and GPT-2 code use it); the layers
run as one scanned, recomputed body (``ops.through_layers``) and batches are
walked in blocks of rows, so that the reference compiles in seconds and fits
on the chip once the program's state is freed.
"""
