"""repro.obs — the unified flight-recorder.

Four layers, all opt-in and cheap when off:

  * host side (:mod:`repro.obs.spans`, :mod:`repro.obs.registry`): timing
    spans and a typed counter/gauge registry that the engine's scattered
    ad-hoc counters collapsed into, streaming JSONL events per process via
    ``REPRO_OBS_DIR`` (:mod:`repro.obs.sink`);
  * in-trace (:class:`ObsConfig` + :class:`repro.core.metrics.RoundDiagnostics`):
    cheap per-round scalar taps computed INSIDE the compiled lattice program,
    gated by a static flag that joins the engine cache key;
  * on the device clock (:mod:`repro.obs.stages`): every span is also a
    ``jax.profiler.TraceAnnotation`` once jax is imported, and each stage of
    the round is a ``jax.named_scope``, so any ``jax.profiler`` trace of a
    run (``jax.profiler.trace(dir)``, ``perfbench --trace 1``) shows the
    host spans and names each device op's stage; the engine registers every
    compiled lattice module's op → stage map here;
  * reporting (:mod:`repro.obs.report`): ``python -m repro.obs.report``
    renders a run's JSONL into summary tables and CI gates.

No module here imports jax at import time — obs sits below ``repro.sim`` in
the layering and stays safe to import before distributed backend init.
"""
from repro.obs.config import DEFAULT_OBS, ObsConfig
from repro.obs.registry import (
    Counter,
    Gauge,
    counter,
    counter_add,
    gauge,
    gauge_set,
    metric_value,
    metrics_snapshot,
    reset_metrics,
)
from repro.obs.sink import (
    ENV_OBS_DIR,
    close_sink,
    emit,
    event_files,
    obs_dir,
    process_coords,
    read_events,
)
from repro.obs.spans import span, span_totals

__all__ = [
    "ObsConfig",
    "DEFAULT_OBS",
    "span",
    "span_totals",
    "Counter",
    "Gauge",
    "counter",
    "gauge",
    "counter_add",
    "gauge_set",
    "metric_value",
    "metrics_snapshot",
    "reset_metrics",
    "emit",
    "obs_dir",
    "process_coords",
    "read_events",
    "event_files",
    "close_sink",
    "ENV_OBS_DIR",
]
