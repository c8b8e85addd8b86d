"""Observability for the pruning engine.

Three parts (strictly host-side):

* :mod:`repro.obs.metrics` — thread-safe counters / gauges /
  histograms in a process-wide ``REGISTRY`` (stdlib only);
* :mod:`repro.obs.trace` — nestable spans on the JAX profiler's clock
  (``jax.profiler.TraceAnnotation``), also exported as Chrome
  trace-event JSON at ``obs="trace"`` (load in Perfetto);
* :mod:`repro.obs.report` — the per-call ``ExecReport`` that engine
  entry points attach to their results, the ``Recorder``/``NULL``
  machinery behind ``ExecOptions.obs="off"|"counters"|"trace"``, and
  :func:`span` for code that has no recorder.

Plus :mod:`repro.obs.log`, the structured ``repro.*`` logger that
replaces ad-hoc ``warnings.warn``/stderr diagnostics (the ``warnings``
channel is kept where tests assert on it).

Invariant: no instrument ever enters jitted code — everything is fed
from materialized outputs, static metadata and host timestamps, so the
engine's masks are bit-identical whatever the obs level.
"""
from . import log, metrics, report, trace
from .log import get_logger
from .metrics import REGISTRY, Registry
from .report import (NULL, OBS_MODES, ExecReport, Recorder, default_level,
                     recorder, set_default_level, span)
from .trace import TRACER, Tracer

__all__ = [
    "log", "metrics", "report", "trace",
    "get_logger",
    "REGISTRY", "Registry",
    "NULL", "OBS_MODES", "ExecReport", "Recorder",
    "default_level", "recorder", "set_default_level", "span",
    "TRACER", "Tracer",
]
