"""Named host spans: one clock for ``FleetResult.timings`` and the
profiler's host timeline.

``span("lp", into=timings, key="lp_s")`` marks ``repro.lp`` on the
profiler's host plane (which shares a clock with the device planes) and
adds the span's ``perf_counter`` seconds to ``timings["lp_s"]``.  Names
are fixed strings: with no profiler running a span costs one
annotation object and two C++ calls.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

__all__ = ["span"]


class span:
    """Mark ``repro.<name>`` around a ``with`` block; when ``into`` is a
    dict, add the block's seconds to ``into[key]``.  ``with`` yields the
    annotation, whose ``set_metadata`` attaches counts to the span on
    the trace."""

    __slots__ = ("_ann", "_into", "_key", "_t0")

    def __init__(self, name: str, into: dict | None = None,
                 key: str | None = None):
        self._ann = TraceAnnotation("repro." + name)
        self._into, self._key = into, key

    def __enter__(self) -> TraceAnnotation:
        self._t0 = time.perf_counter()
        return self._ann.__enter__()

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        if self._into is not None:
            self._into[self._key] = (self._into.get(self._key, 0.0)
                                     + time.perf_counter() - self._t0)
