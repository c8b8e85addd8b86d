"""Per-call ``ExecReport`` + the ``Recorder`` that engine entries use.

Every instrumented entry point (``engine_prune``, ``engine_prune_batch``,
``PruneStream``, ``run_query``, ``planner.tune``, ``serve.engine``)
creates a :func:`recorder` at the top, feeds it host-side facts while
it runs, and attaches ``rec.finish()`` — an :class:`ExecReport` — to
its result. The recorder mirrors everything into the process-wide
``repro.obs.metrics.REGISTRY`` under ``f"{entry}.{name}"`` keys. Its
spans (``rec.span("pass1")`` -> ``engine_prune.pass1``) are profiler
annotations at every level but ``"off"``; in ``"trace"`` mode they also
record wall-clock events on ``repro.obs.trace.TRACER``. Code with no
recorder opens the same spans with :func:`span`.

The no-instruments-inside-jit rule, concretely:

* counters are fed from already-materialized outputs
  (``int(np.asarray(mask).sum())``) and static metadata (``x.nbytes``),
  never from values still owned by a trace;
* when the entry itself runs under ``jax.jit`` (its streams are
  ``Tracer`` objects) the factory degrades to the shared :data:`NULL`
  no-op recorder, so no host callback ever lands in a jaxpr;
* ``obs="off"`` also returns :data:`NULL` — a singleton whose methods
  are all constant-time no-ops, keeping the fast path strict.

Masks are therefore bit-identical with obs off, on, or tracing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any

from . import metrics, trace

# valid values for ExecOptions.obs / set_default_level
OBS_MODES = ("off", "counters", "trace")

_default_level = "counters"


def default_level() -> str:
    """Process-wide obs level used when ``ExecOptions.obs`` is unset."""
    return _default_level


def set_default_level(level: str) -> None:
    if level not in OBS_MODES:
        raise ValueError(
            f"obs level must be one of {OBS_MODES}, got {level!r}")
    global _default_level
    _default_level = level


_OFF = contextlib.nullcontext()


def span(name: str, level: str | None = None):
    """A named host span for code that has no recorder (the suite's
    query bodies, query completion): a profiler annotation, plus a
    wall-clock event on ``TRACER`` at ``"trace"``; a no-op at ``"off"``.
    ``level`` defaults to the process-wide level. Never syncs."""
    lvl = _default_level if level is None else level
    if lvl == "off":
        return _OFF
    if lvl == "trace":
        return trace.TRACER.span(name)
    return trace.annotation(name)


@dataclasses.dataclass
class ExecReport:
    """What one engine call did: counters, spans, and identity.

    ``counters`` holds this call's local values (the registry holds the
    process-wide running totals); ``spans`` the Chrome trace events this
    call emitted (empty unless ``obs="trace"``).
    """

    entry: str
    meta: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    wall_us: float = 0.0

    def _c(self, name, default=0):
        return self.counters.get(name, default)

    @property
    def entries_scanned(self) -> int:
        return int(self._c("entries_scanned"))

    @property
    def entries_kept(self) -> int:
        return int(self._c("entries_kept"))

    @property
    def prune_ratio(self) -> float:
        return float(self._c("prune_ratio", 0.0))

    @property
    def state_bytes_shipped(self) -> int:
        return int(self._c("state_bytes_shipped"))

    @property
    def merge_collective_count(self) -> int:
        return int(self._c("merge_collective_count"))

    def summary(self) -> str:
        """Pretty-printed per-query summary (one string, many lines)."""
        lines = [f"ExecReport[{self.entry}]"]
        for k, v in sorted(self.meta.items()):
            lines.append(f"  {k:<26} {v}")
        for k in sorted(self.counters):
            v = self.counters[k]
            if isinstance(v, float):
                lines.append(f"  {k:<26} {v:.4f}")
            else:
                lines.append(f"  {k:<26} {v}")
        if self.wall_us:
            lines.append(f"  {'wall_us':<26} {self.wall_us:.1f}")
        for ev in self.spans:
            depth = int(ev.get("args", {}).get("depth", 0))
            lines.append(
                f"  span {'  ' * depth}{ev['name']:<{22 - 2 * depth}} "
                f"{ev['dur']:>10.1f} us")
        return "\n".join(lines)


class _NullRecorder:
    """The strict no-op fast path (``obs="off"`` / called under jit)."""

    __slots__ = ()
    active = False
    level = "off"

    def count(self, name, value=1):
        pass

    def observe(self, name, value):
        pass

    def annotate(self, **meta):
        pass

    def span(self, name, **args):
        return _OFF

    def sync(self, x):
        return x

    def finish(self):
        return None


NULL = _NullRecorder()


class Recorder:
    """Accumulates one call's telemetry; ``finish()`` -> ExecReport."""

    active = True

    def __init__(self, entry: str, level: str,
                 registry: metrics.Registry | None = None,
                 tracer: trace.Tracer | None = None):
        self.entry = entry
        self.level = level
        self.registry = metrics.REGISTRY if registry is None else registry
        self.tracer = trace.TRACER if tracer is None else tracer
        self.counters: dict[str, Any] = {}
        self.meta: dict[str, Any] = {}
        self.spans: list[dict] = []
        self._t0 = time.perf_counter()
        self.registry.record(f"{entry}.dispatch_count", 1)

    def count(self, name: str, value=1) -> None:
        """Add to a local counter and the registry (``*_ratio`` names
        are last-value gauges in both)."""
        if name.endswith("_ratio"):
            self.counters[name] = float(value)
        else:
            self.counters[name] = self.counters.get(name, 0) + value
        self.registry.record(f"{self.entry}.{name}", value)

    def observe(self, name: str, value) -> None:
        """Feed a registry histogram; locally keep last/max."""
        self.registry.histogram(f"{self.entry}.{name}").observe(value)
        self.counters[f"{name}_max"] = max(
            self.counters.get(f"{name}_max", value), value)

    def annotate(self, **meta) -> None:
        self.meta.update(meta)

    def span(self, name: str, **args):
        """The span ``<entry>.<name>``: a profiler annotation; in trace
        mode also a wall-clock event (with ``args``) on the tracer and
        in this call's report."""
        if self.level != "trace":
            return trace.annotation(f"{self.entry}.{name}")
        return self.tracer.span(f"{self.entry}.{name}", args=args or None,
                                sink=self.spans)

    def sync(self, x):
        """In trace mode, block on ``x`` so span walls measure compute,
        not dispatch. Identity otherwise; never changes values."""
        if self.level == "trace" and x is not None:
            import jax
            jax.block_until_ready(x)
        return x

    def finish(self) -> ExecReport:
        self.counters.setdefault("merge_collective_count", 0)
        if ("entries_scanned" in self.counters
                and "entries_kept" in self.counters
                and "prune_ratio" not in self.counters):
            scanned = self.counters["entries_scanned"]
            kept = self.counters["entries_kept"]
            ratio = 1.0 - kept / scanned if scanned else 0.0
            self.count("prune_ratio", ratio)
        wall = (time.perf_counter() - self._t0) * 1e6
        return ExecReport(entry=self.entry, meta=dict(self.meta),
                          counters=dict(self.counters),
                          spans=list(self.spans), wall_us=wall)


def _has_jax_tracer(streams) -> bool:
    try:
        import jax
    except Exception:
        return False
    return any(isinstance(s, jax.core.Tracer) for s in jax.tree_util
               .tree_leaves(streams))


def recorder(entry: str, level: str | None = None, streams=()):
    """The factory every instrumented entry point calls.

    Returns :data:`NULL` when obs is off **or** any leaf of ``streams``
    is a jax ``Tracer`` (the caller is itself being traced/jitted —
    host-side accumulation there would bake stale values into the
    compiled program).
    """
    lvl = _default_level if level is None else level
    if lvl not in OBS_MODES:
        raise ValueError(f"obs level must be one of {OBS_MODES}, got {lvl!r}")
    if lvl == "off" or _has_jax_tracer(streams):
        return NULL
    return Recorder(entry, lvl)
