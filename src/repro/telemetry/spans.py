"""Program spans on the profiler's clock.

A span marks one stretch of host work at a layer boundary of the
program: `workloads.generate`, `fleetsim.compile`, `engine.decode`.
It is a `jax.profiler.TraceAnnotation`, so under `jax.profiler.trace`
it lands in the same profile as the device's planes, on the same
clock; its counts (integers or floats) ride along as the event's
arguments, which a reader finds in `ProfileData` as `event.stats`.
With no trace active a span costs about a microsecond.

Names are `<layer>.<what>`, the layer one of `workloads`, `sim`,
`fleetsim` or `engine`.

`Phase` is a span that is also timed on the host clock. Given a hub,
it sets the hub's `phase/<gauge>_wall_s` gauge from the same reading,
so the wall-time gauges and the profile agree.

Importing this module does not import jax. A profile can only be
running in a process that has loaded jax, so until jax is loaded a
span is a no-op and jax stays unloaded (the event engine never needs
it).
"""
from __future__ import annotations

import sys
import time

__all__ = ["Phase", "span"]

_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set_metadata(self, **stats) -> None:
        pass


_NULL_SPAN = _NullSpan()


def span(name: str, **stats):
    """A context manager for one span named `name` (`<layer>.<what>`),
    with `stats` as its arguments. Arguments known only at the end go
    in through the returned object's `set_metadata(**stats)`."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _NULL_SPAN
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name, **stats)


class Phase:
    """A timed span: `with phase:` opens the span `name` and adds the
    host seconds it took to `wall_s`. Given a hub, each exit sets the
    gauge `phase/<gauge>_wall_s` to `wall_s`, the phase's total over
    every time it was entered."""

    __slots__ = ("name", "hub", "gauge", "wall_s", "_span", "_t0")

    def __init__(self, name: str, hub=None, gauge: str | None = None):
        self.name = name
        self.hub = hub
        self.gauge = f"phase/{gauge}_wall_s" if gauge else None
        self.wall_s = 0.0

    def __enter__(self) -> "Phase":
        self._span = span(self.name)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s += time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        if self.hub is not None and self.gauge is not None:
            self.hub.set_gauge(self.gauge, self.wall_s)
