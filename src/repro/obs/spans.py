"""Host-side timing spans: ``with span("lattice.compile"): ...``.

A span measures one wall-clock interval, accumulates it into the registry
(``span.<name>.count`` / ``span.<name>.seconds`` — so totals are queryable
in-process without replaying the sink) and streams one ``span`` event per
exit to the JSONL sink. Usable as a context manager or a decorator
(:class:`span` subclasses ``ContextDecorator``).

Spans nest: each event carries ``parent``, the name of the span open
around it in the same thread or task (None at the top), so a report can
tell ``lattice.dispatch`` inside ``lattice.sweep`` from one outside it.

Spans time HOST work (trace, compile, dispatch, stream-out). Once jax is
imported, a span also enters a ``jax.profiler.TraceAnnotation`` of its
name, so a profiler trace taken around the run (``jax.profiler.trace``, or
``perfbench --trace 1``) shows every span on the same clock as the device
ops it waited for. With no profiler running an annotation costs about a
microsecond. This module never imports jax itself.
"""
from __future__ import annotations

import sys
import time
from contextlib import ContextDecorator
from contextvars import ContextVar

from repro.obs.registry import counter_add
from repro.obs.sink import emit

# names of the spans open in this thread/task, outermost first
_OPEN: ContextVar[tuple[str, ...]] = ContextVar("repro_obs_open_spans", default=())


class span(ContextDecorator):
    """Time one interval under a dotted name, with optional static fields.

    ``fields`` are attached to the emitted event verbatim (keep them
    JSON-serializable scalars); :meth:`annotate` adds more from inside the
    block. Exceptions propagate — the span still records, stamped with
    ``error`` — so instrumenting a call site never changes its control flow.
    """

    def __init__(self, name: str, **fields):
        self.name = name
        self.fields = fields
        self.seconds: float | None = None  # set on exit
        self.parent: str | None = None     # set on entry
        self._t0: float | None = None
        self._token = None
        self._annotation = None

    def _recreate_cm(self) -> "span":
        # a decorated function gets a fresh span per call, so recursive and
        # concurrent calls each keep their own timing and nesting state
        return span(self.name, **self.fields)

    def annotate(self, **fields) -> "span":
        self.fields.update(fields)
        return self

    def __enter__(self) -> "span":
        open_spans = _OPEN.get()
        self.parent = open_spans[-1] if open_spans else None
        self._token = _OPEN.set(open_spans + (self.name,))
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        _OPEN.reset(self._token)
        counter_add(f"span.{self.name}.count", 1, emit_event=False)
        counter_add(f"span.{self.name}.seconds", self.seconds, emit_event=False)
        if exc_type is not None:
            self.fields.setdefault("error", exc_type.__name__)
        emit(
            "span", self.name, seconds=round(self.seconds, 6),
            parent=self.parent, **self.fields,
        )
        return False  # never swallow exceptions


def span_totals(name: str) -> dict:
    """In-process totals for one span name: ``{"count", "seconds"}``."""
    from repro.obs.registry import metric_value

    return {
        "count": metric_value(f"span.{name}.count"),
        "seconds": metric_value(f"span.{name}.seconds"),
    }
