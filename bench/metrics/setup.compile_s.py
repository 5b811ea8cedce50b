"""Seconds JAX spent in set-up tracing, lowering and compiling programs or
loading them from the persistent cache, summed from its own compile
events."""


def read(run):
    return run.compile_s if run.compile_s > 0 else None
