"""Seconds of set-up JAX spent tracing, lowering and compiling or loading
programs from the persistent cache (``jax.monitoring`` durations)."""


def read(run):
    return run.setup_compile_s
