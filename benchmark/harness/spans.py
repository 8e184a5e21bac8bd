"""The benchmark's own host spans around its calls into the program's
layers, written into the profiler's trace (``jax.profiler``
``TraceAnnotation``; without a profiler session each costs a few
microseconds)."""

from __future__ import annotations


def wrap(module, attr: str, span: str):
    """Run ``module.attr`` inside the span ``bench.<span>``; returns the
    call that puts the original back."""
    import jax.profiler

    orig = getattr(module, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation("bench." + span):
            return orig(*a, **kw)

    setattr(module, attr, wrapped)
    return lambda: setattr(module, attr, orig)
