"""JAX traces per iteration inside the window: the program's
``jax.trace:<function>`` spans (``obs.compile_listener``, one per
``/jax/core/compile/jaxpr_trace_duration`` event, the same events that
its registry counts as ``jax/traces``) over the window's iterations.  0
when nothing is traced after the warm-up.  Nothing where the window has
no ``prefetch.slice`` span: the program then predates its compile
listener, which came with that span."""


def read(w):
    spans = w.spans or ()
    if not any(e.name == "prefetch.slice" for e in spans):
        return None
    n = sum(1 for e in spans if e.name.startswith("jax.trace:"))
    return n / w.iterations
