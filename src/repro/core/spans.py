"""Named host spans at the layer boundaries of the tuning path.

``with span("repro.sim.trace", epochs=60, pages=n) as sp: ...`` opens a
``jax.profiler.TraceAnnotation`` of that name and times the block on
``time.perf_counter``: ``sp.s`` holds its seconds once the block has left.
Counts are plain ints already at hand (shapes, byte counts, flags); one
known only inside the block is added with ``sp.count(name=value)``.  They
ride on the span as its arguments, which the profiler's trace keeps as the
event's stats, on the same clock as the device's events.

Spans reach a trace exactly when the JAX profiler is running; nothing else
turns them on.  With the profiler off a span costs one annotation and two
clock reads, and no span waits for the device.  The profiler can only run
in a process that has imported jax, so where jax is not imported a span is
the clock alone and ``import repro.core`` stays free of jax.

``round_of(i)`` marks BO round ``i`` as running; spans opened inside it
that do not know the round themselves (``repro.sim.run``) read it from
:func:`current_round`, -1 outside every round.
"""

from __future__ import annotations

import contextlib
import sys
import time


class span:
    """A named host span (see the module docstring)."""

    __slots__ = ("name", "s", "_counts", "_note", "_t0")

    def __init__(self, name: str, **counts: int):
        self.name = name
        self.s = 0.0
        self._counts = counts
        self._note = None

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        if jax is not None:
            self._note = jax.profiler.TraceAnnotation(self.name,
                                                      **self._counts)
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def count(self, **counts: int) -> None:
        """Attach counts that are known only inside the span."""
        if self._note is not None:
            self._note.set_metadata(**counts)

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(*exc)


_round = -1


def current_round() -> int:
    """The BO round now running, or -1 outside every round."""
    return _round


@contextlib.contextmanager
def round_of(i: int):
    """Mark BO round ``i`` as running for the spans opened inside."""
    global _round
    prev, _round = _round, int(i)
    try:
        yield
    finally:
        _round = prev
