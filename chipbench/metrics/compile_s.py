"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) during set-up, by the host clock of JAX's own compile
events."""


def read(run):
    return run.compile_s
