"""The repository's benchmark: one cell, once, per process (``run.py``).

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the plain references, the FLOP and
byte counts, the peaks table, the reduction from traces to metrics and the
comparison that decides ``correct``.  From ``deepspeed_tpu`` it takes only
the system under test (``deepspeed_tpu.initialize`` + ``train_batch``,
``InferenceEngine.submit/step``), its compile counters and its kernel
names.  Nothing here imports ``deepspeed_tpu.profiling``.
"""
