"""Seconds XLA spent compiling during set-up (JAX's compile-duration
events; a persistent-cache hit compiles nothing)."""


def read(run):
    return run.compile_s
