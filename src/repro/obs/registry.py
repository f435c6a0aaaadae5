"""The in-process metrics registry: typed counters and gauges.

One flat namespace of dotted metric names (``engine_cache.hits``,
``lattice.n_compiles``, ``span.lattice.compile.seconds``) holding plain
numbers. This registry is what the engine's five generations of ad-hoc
counters collapsed into: ``repro.sim.engine.engine_cache_stats``,
``repro.sim.compile_cache.persistent_cache_counters`` and friends are now
thin shims reading it, and every mutation can stream to the JSONL sink
(``repro.obs.sink``) so a run's counter history is replayable offline.

Reset semantics — the part the old scattered counters never agreed on:

  * :func:`reset_metrics` with a ``prefix`` zeroes exactly that namespace
    (``reset_engine_cache`` resets ``engine_cache.``, nothing else);
  * :func:`reset_metrics` with no prefix zeroes everything — including the
    process-lifetime persistent-compile-cache counters, so code that reads
    them across a whole run should never share a process with an unscoped
    full reset (tests use prefix resets).

No jax imports; safe from anywhere.
"""
from __future__ import annotations

import threading
from typing import Union

from repro.obs.sink import emit

Number = Union[int, float]

_METRICS: dict[str, Number] = {}
# increments can fire from jitted-function trace bodies and listener
# callbacks; keep them atomic under any threaded caller
_LOCK = threading.Lock()


def counter_add(name: str, delta: Number = 1, emit_event: bool = True) -> Number:
    """Add ``delta`` to counter ``name`` (created at 0) and return the new
    total. Streams a ``counter`` event to the sink unless ``emit_event`` is
    False (span bookkeeping passes False — the span event already carries
    the same numbers)."""
    with _LOCK:
        total = _METRICS.get(name, 0) + delta
        _METRICS[name] = total
    if emit_event:
        emit("counter", name, delta=delta, total=total)
    return total


def gauge_set(name: str, value: Number, emit_event: bool = True) -> Number:
    """Set gauge ``name`` to ``value`` (last write wins)."""
    with _LOCK:
        _METRICS[name] = value
    if emit_event:
        emit("gauge", name, value=value)
    return value


def metric_value(name: str, default: Number = 0) -> Number:
    """Current value of one metric (``default`` when never touched)."""
    return _METRICS.get(name, default)


def metrics_snapshot(prefix: str = "") -> dict:
    """Copy of every metric whose name starts with ``prefix``."""
    with _LOCK:
        return {k: v for k, v in _METRICS.items() if k.startswith(prefix)}


def reset_metrics(prefix: str = "") -> None:
    """Zero (drop) every metric under ``prefix``; no prefix drops all."""
    with _LOCK:
        for k in [k for k in _METRICS if k.startswith(prefix)]:
            del _METRICS[k]


class Counter:
    """Typed handle on one monotonically-increasing registry counter."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def add(self, delta: Number = 1) -> Number:
        return counter_add(self.name, delta)

    @property
    def value(self) -> Number:
        return metric_value(self.name)


class Gauge:
    """Typed handle on one last-write-wins registry gauge."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def set(self, value: Number) -> Number:
        return gauge_set(self.name, value)

    @property
    def value(self) -> Number:
        return metric_value(self.name)


def counter(name: str) -> Counter:
    """A :class:`Counter` handle for ``name`` (registered lazily at first add)."""
    return Counter(name)


def gauge(name: str) -> Gauge:
    """A :class:`Gauge` handle for ``name``."""
    return Gauge(name)
