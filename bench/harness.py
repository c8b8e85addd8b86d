"""One run of one cell: set up, measure, check, report.

``run_cell`` is what ``run.py`` calls; the tests call it too, with
``require_tpu=False`` and a small ``scale``. Everything a cell needs is
found by name from ``BENCHMARK.json`` (see ``bench/__init__.py``).
"""
from __future__ import annotations

import contextlib
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

import numpy as np

from bench import loops, reduce_trace, reference, systems

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_parts(spec: dict, workload: str):
    """(cell, config entry, config file, traffic) of one workload."""
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    centry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = _json(os.path.join(ROOT, centry["file"]))
    traffic = _json(os.path.join(BENCH_DIR, "traffic",
                                 cell["traffic"] + ".json"))
    return cell, centry, cfg, traffic


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


def load_metric(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    mod_name = "bench_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    loader = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(mod)
    return mod


def load_layers() -> dict:
    """{layer name: [module-name patterns]} from ``layers/*.json``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "layers",
                                              "*.json"))):
        d = _json(path)
        out[d["layer"]] = d["modules"]
    return out


def load_peaks() -> dict:
    """{device_kind: peaks} from ``peaks/*.json``."""
    rows = (_json(p) for p in glob.glob(os.path.join(BENCH_DIR, "peaks",
                                                    "*.json")))
    return {r["device_kind"]: r for r in rows}


def scaled(cfg: dict, scale: float | None) -> dict:
    """The configuration at ``scale`` times its scale factor (tests
    only; a run on the chip uses the scale as stated)."""
    if scale is None:
        return cfg
    return dict(cfg, scale_factor=cfg["scale_factor"] * scale)


class Context:
    """What a metric reader sees: the window's records, the set-up
    time, the trace's reduction and the device's peaks."""

    def __init__(self, window, setup_s, trace, peaks, traffic, work):
        self.window, self.setup_s, self.trace = window, setup_s, trace
        self.peaks, self.traffic, self.work = peaks, traffic, work


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


MAX_WARM_BATCHES = 80


def warm_streams(system, batch: dict) -> None:
    """Fold ``batch`` until every stream has merged once (at most
    ``MAX_WARM_BATCHES`` times), then once more against a merged state,
    so that fold, merge and apply are compiled; then reset the streams."""
    for _ in range(MAX_WARM_BATCHES):
        [np.asarray(m) for m in system.fold(batch)[0]]
        if system.merged_everywhere():
            break
    [np.asarray(m) for m in system.fold(batch)[0]]
    system.reset()


class CompileCounter:
    """Compilations (cache hits included) seen while ``counting``."""

    def __init__(self, jax):
        self.counting = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.counting and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             use_cache: bool = True, scale: float | None = None,
             wrap=None, control: bool = False, log=print) -> dict:
    """Run one cell once; returns the result line as a dict.

    wrap: breaks the program's timed path (``systems.FAULTS``);
    control: puts the lower-precision reference in the program's place.
    """
    spec = load_spec()
    cell, _, cfg, traffic = cell_parts(spec, workload)
    cfg = scaled(cfg, scale)

    import jax

    device = device_info(jax, cell["chips"], require_tpu)
    counter = None
    if use_cache:
        from repro import compile_cache

        cache_dir = compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        counter = compile_cache.CacheCounter()
        log(f"compile cache: {cache_dir}")
    compiles = CompileCounter(jax)

    # ------------------------------------------------------------ set-up
    schema = importlib.import_module("bench.schemas." + cfg["schema"])
    closed = traffic["loop"] == "closed"
    if closed:
        data = schema.make_tables(cfg, seed)
        names = list(dict.fromkeys(traffic["queries"]))
        system = (systems.ControlQueries(cfg, data) if control
                  else systems.ProgramQueries(data, names))
        for name in names:
            system.run(name)          # compiles this cell's shapes
    else:
        length = traffic["trace_seconds"] if trace else seconds
        b = traffic["micro_batch"]
        n = max(int(traffic["rate_per_s"] * length) // b * b, b)
        data = schema.make_stream(cfg, seed, traffic["table"], n,
                                  [s["column"] for s in traffic["streams"]])
        system = (systems.ControlStreams(traffic["streams"]) if control
                  else systems.ProgramStreams(traffic["streams"]))
        warm_streams(system, {c: v[:b] for c, v in data.items()})
    if wrap is not None:
        system = wrap(system)
    setup_s = loops.clock() - t_start
    log(f"setup: {setup_s:.3f} s (tables/events from seed {seed}, "
        f"every shape of the mix warmed)")

    # ------------------------------------------------------------ window
    compiles.counting = True
    if not trace:
        window = (loops.closed_loop(system, traffic["queries"],
                                    seconds=seconds) if closed else
                  loops.open_loop(system, traffic, data, seconds=seconds))
    else:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        span = jax.profiler.TraceAnnotation
        start = lambda: jax.profiler.start_trace(trace_dir,
                                                 profiler_options=opts)
        if closed and "trace_cut_s" in traffic:
            window = loops.closed_loop_cut(
                system, traffic["queries"], lead_s=traffic["trace_lead_s"],
                cut_s=traffic["trace_cut_s"], start=start,
                stop=jax.profiler.stop_trace, span=span)
        else:
            start()
            try:
                with span("bench.window"):
                    window = (
                        loops.closed_loop(system, traffic["queries"],
                                          seconds=traffic["trace_seconds"],
                                          span=span) if closed else
                        loops.open_loop(system, traffic, data, span=span,
                                        seconds=traffic["trace_seconds"]))
            finally:
                jax.profiler.stop_trace()
    compiles.counting = False
    peak = memory_peak(jax, cell["chips"])
    device["memory_peak_bytes"] = peak
    if counter is not None:
        log(f"compile cache: hits={counter.hits} writes={counter.writes} "
            f"compiles_in_window={compiles.count}")
    else:
        log(f"compiles_in_window={compiles.count}")
    if not closed:
        late = window["lateness_s"] * 1e3
        log(f"generator: {len(late)} batches, late p50="
            f"{float(np.median(late)):.3f} ms p95="
            f"{float(np.percentile(late, 95)):.3f} ms max="
            f"{float(late.max()):.3f} ms")
    else:
        for name in dict.fromkeys(traffic["queries"]):
            took = [b - a for n, a, b in window["queries"] if n == name]
            if not took:
                continue
            log(f"{name}: {len(took)} queries, median "
                f"{float(np.median(took)) * 1e3:.3f} ms, max "
                f"{max(took) * 1e3:.3f} ms")
    if hasattr(system, "describe"):
        log(system.describe())
    del system

    # ------------------------------------------------------------ trace
    reduced = None
    if trace:
        try:
            reduced = reduce_trace.reduce_dir(trace_dir, load_layers(),
                                              window="bench.window")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    # ------------------------------------------------------------ check
    if closed:
        work = {name: reference.work(name, data)
                for name in dict.fromkeys(traffic["queries"])}
        compared, attempted, failed = reference.check_queries(
            cfg, data, window["answers"])
    else:
        work = {}
        compared, attempted, failed = reference.check_streams(
            traffic, data, window)

    # ------------------------------------------------------------ metrics
    ctx = Context(window, setup_s, reduced, load_peaks().get(device["kind"]),
                  traffic, work)
    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = attempted > 0 and all(
        c["value"] <= c["limit"] for c in compared.values())
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = reduced["breakdown"]
    out["compared"] = compared
    return out


def print_result(out: dict, stream=sys.stdout) -> None:
    """The compared numbers as the last lines of stderr, then the
    result as the last line of stdout."""
    for name, c in out["compared"].items():
        print(f"compared {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), file=stream, flush=True)


@contextlib.contextmanager
def quiet_stdout():
    """Send stray prints of the program to stderr, so the result line
    stays the last line of stdout."""
    old = sys.stdout
    sys.stdout = sys.stderr
    try:
        yield old
    finally:
        sys.stdout = old
