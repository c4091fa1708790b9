"""Seconds JAX spent tracing, lowering and compiling during set-up (its
``/jax/core/compile/`` monitoring events); small where the persistent
cache in the checkout hit."""


def read(run):
    return run.counters.get("compile_s")
