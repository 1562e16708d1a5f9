"""Program spans on the profiler's clock.

``span(name, **args)`` marks one stretch of host work.  While a JAX
profiler session records (``jax.profiler.trace``/``start_trace``), it is
a ``jax.profiler.TraceAnnotation``: the span lands in the same
``.xplane.pb`` as the device operations, on the same time base, on the
line of the thread that ran it, with ``args`` as its metadata.  While no
session records, it is one shared no-op context, so a span costs one
call and one flag test.  Nothing else turns spans on or off.

    with span("mvstore.publish", words=n) as sp:
        ...
        sp.set(outcome="commit")      # metadata known only at the end

Span names are dotted by layer (``tm.``, ``mvstore.``, ``mvcontroller.``,
``kernel.``); ``bench/span_report.py`` reads them by those prefixes.
Keep spans off per-word and per-element loops: one span per call.
"""
from __future__ import annotations

import itertools

from jax.profiler import TraceAnnotation

__all__ = ["next_op", "span"]

_recording = TraceAnnotation.is_enabled


class _Off:
    """The span while no profiler records: enters, exits, drops args."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


class _On(TraceAnnotation):
    """The span while a profiler records."""

    def set(self, **args) -> None:
        """Attach ``args`` to the span; they are written when it ends."""
        self.set_metadata(**args)


_OFF = _Off()
_ops = itertools.count(1)


def span(name: str, **args):
    """A context manager marking ``name`` in the profiler's trace while a
    profiler records, else a shared no-op; both have ``set(**args)``."""
    if not _recording():
        return _OFF
    return _On(name, **args)


def next_op() -> int:
    """A fresh operation number, unique in the process: the id that the
    spans of one logical operation (all its attempts) share."""
    return next(_ops)
