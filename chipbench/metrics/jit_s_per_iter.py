"""Seconds per iteration inside the window that JAX spent tracing,
lowering and compiling (or loading from the persistent compilation cache):
the sum of its ``/jax/core/compile/*_duration`` events (``jax.monitoring``)
over the window's iterations.  0 where nothing is traced or compiled
after the warm-up."""


def read(w):
    return w.compile_s / w.iterations
