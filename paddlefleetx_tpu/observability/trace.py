"""Host phases on the clock the device trace is on.

``annotate(name, record)`` is the one call every host phase uses. It
opens a ``jax.profiler.TraceAnnotation`` (a TraceMe: recorded only
while a profiler session runs, on the session's clock and on the
calling thread's line, so the ``.xplane.pb`` shows the phase beside
the device's op line) and adds the elapsed ``perf_counter`` seconds
to the caller's per-step ``record`` under the same name — one
interval, clocked once, feeding the trace and the always-on
accounting alike. With no session running it costs about a
microsecond.

``point(template, *values)`` is its sibling for a fact with no
duration: one TraceMe of next to no length whose NAME is
``template % values``, on the same clock and line, so what a step
counted sits in the trace directly after the interval it belongs to.
The name is only formatted while a session records; with none the
call is one ``TraceMe.is_enabled()``.

Names are slash-paths whose parent is their prefix
(``serving/step/admit`` lies inside ``serving/step``, ``h2d/pretreat``
inside ``h2d``), so nesting can be rebuilt from names and intervals
alone. The vocabulary is the "Host phases" table of
``docs/observability.md``.
"""

from __future__ import annotations

import time
from typing import MutableMapping, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation


class annotate:
    """Context manager labeling the enclosed host block ``name`` in
    the profiler timeline; on exit the block's seconds are in
    ``.seconds`` and added to ``record[name]`` (a phase entered twice
    in one step sums)."""

    __slots__ = ("name", "seconds", "_record", "_ann", "_t0")

    def __init__(self, name: str,
                 record: Optional[MutableMapping[str, float]] = None):
        self.name = name
        self.seconds = 0.0
        self._record = record
        self._ann = TraceAnnotation(name)

    def __enter__(self) -> "annotate":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._record is not None:
            self._record[self.name] = \
                self._record.get(self.name, 0.0) + self.seconds


def point(template: str, *values) -> None:
    """Mark the instant ``template % values`` on the calling thread's
    line of a running profiler session; nothing, and nothing
    formatted, with no session. The values ride in the name because a
    reduction that keeps ``(name, start, duration)`` of an event drops
    a TraceMe's metadata."""
    if TraceAnnotation.is_enabled():
        with TraceAnnotation(template % values):
            pass


def annotate_step(name: str, step_num: int) -> StepTraceAnnotation:
    """One training step for the profiler: the device plane's step
    line then carries ``step_num`` for what was launched inside."""
    return StepTraceAnnotation(name, step_num=step_num)


def unaccounted(record: MutableMapping[str, float], root: str) -> float:
    """Seconds of ``record[root]`` that none of its direct children
    (``root/<phase>``) covers."""
    depth = root.count("/") + 1
    return record.get(root, 0.0) - sum(
        v for k, v in record.items()
        if k.startswith(root + "/") and k.count("/") == depth)
