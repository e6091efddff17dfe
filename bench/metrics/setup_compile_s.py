"""Seconds JAX spent tracing, lowering and compiling during set-up
(jax.monitoring events, cache hits included)."""


def read(run):
    return run.setup_compile_s
