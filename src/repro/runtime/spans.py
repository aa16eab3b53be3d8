"""Host spans of the program on the profiler's clock.

``span(name)`` is a context manager around one stage of host work. While a
profiler session is active (``TraceAnnotation.is_enabled()``) it opens a
``jax.profiler.TraceAnnotation``: the span lands on the trace's host plane,
on the same clock as the device planes, and ``set(**counts)`` attaches its
counters as the event's stats. Otherwise it returns one shared no-op whose
``live`` is False, so an untraced run pays one check per span; compute
counters only under ``if sp.live``.

Names are ``cg.<layer>.<stage>``; ``cb.`` is the chip benchmark's own
prefix and is never used here. Spans open in host code only, never inside
a jitted function, so they change no compiled program.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


class _Off:
    """The span of an untraced run: does nothing."""

    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **counts) -> None:
        pass


_OFF = _Off()


class _On:
    """A span in an active profiler session."""

    live = True

    def __init__(self, name: str):
        self._annotation = TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        return self._annotation.__exit__(*exc)

    def set(self, **counts) -> None:
        self._annotation.set_metadata(**counts)


def span(name: str):
    """A host span named ``name``; the shared no-op when nothing traces."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _On(name)
