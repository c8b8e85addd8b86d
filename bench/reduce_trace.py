"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* window: the host span named by the caller (``bench.window``), on the
  profiler's clock.
* busy: the union of the intervals in which an operation (``XLA Ops``
  and ``Async XLA Ops``) ran on a device inside the window, averaged over
  the devices that ran any; idle share is 1 - busy / window.
* layers: device time of the XLA modules (programs) each layer's
  patterns match (``layers/*.json``), clipped to the window, summed over
  devices.
* breakdown: the programs that took most device time (operations, where
  no program began inside the window), and the longest idle gaps, each
  named by the innermost host span of the benchmark's own thread that
  covers it.

The TPU profiler records every operation and keeps about 6.3M events a
session (a 9 s Q1 query's one-lane scan emits ~18M, and the trace stops
at ~3.3 s); every traffic mix sizes its traced window to stay under it.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"


def _events(line):
    """(names, starts, ends) of a line's events, ns."""
    names, starts, ends = [], [], []
    for e in line.events:
        names.append(e.name)
        starts.append(e.start_ns)
        ends.append(e.end_ns)
    return names, np.asarray(starts, float), np.asarray(ends, float)


def _union(starts, ends):
    """Merged, sorted intervals covering the given ones."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def _clipped(events, lo, hi):
    """(names, durations) of the events overlapping [lo, hi], clipped."""
    names, s, e = events
    d = np.clip(e, lo, hi) - np.clip(s, lo, hi)
    inside = np.flatnonzero(d > 0)
    return [names[i] for i in inside], d[inside] * 1e-9


def _short(op: str) -> str:
    """An operation's name without its HLO text."""
    return op.split(" = ", 1)[0]


def reduce_file(path: str, layers: dict, window: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans = None         # the events of the thread that opened window
    devices = []              # per device: (ops events, modules events)
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops = [_events(lines[n]) for n in OPS_LINES if n in lines]
            if ops:
                merged = (sum((o[0] for o in ops), []),
                          np.concatenate([o[1] for o in ops]),
                          np.concatenate([o[2] for o in ops]))
                mods = (_events(lines[MODULES_LINE])
                        if MODULES_LINE in lines else ([], np.zeros(0),
                                                       np.zeros(0)))
                devices.append((merged, mods))
        elif plane.name.startswith("/host:") and host_spans is None:
            for ln in plane.lines:
                n, s, e = _events(ln)
                if window in n:
                    host_spans = list(zip(n, s, e))
                    break
    if host_spans is None:
        raise ValueError(f"no host span {window!r} in {path}")
    lo, hi = next((s, e) for n, s, e in host_spans if n == window)

    busy, gaps, by_name, programs = [], [], {}, []
    layer_s = dict.fromkeys(layers, 0.0)
    pats = {k: [re.compile(p) for p in v] for k, v in layers.items()}
    for (names, s, e), mods in devices:
        cs, ce = np.clip(s, lo, hi), np.clip(e, lo, hi)
        inside = ce > cs
        if not inside.any():
            continue
        us, ue = _union(cs[inside], ce[inside])
        busy.append(float((ue - us).sum()) * 1e-9)
        gaps.extend((a, b) for a, b in zip(np.concatenate([[lo], ue]),
                                           np.concatenate([us, [hi]]))
                    if b > a)
        programs.extend(zip(*mods))
        mod_names, mod_d = _clipped(mods, lo, hi)
        for name, d in zip(mod_names, mod_d):
            by_name[name] = by_name.get(name, 0.0) + d
            for layer, ps in pats.items():
                if any(p.search(name) for p in ps):
                    layer_s[layer] += d
        if not mod_names:
            for name, d in zip(*_clipped((names, s, e), lo, hi)):
                key = _short(name)
                by_name[key] = by_name.get(key, 0.0) + d
    if not busy:
        raise ValueError(f"no device operation inside {window!r} in {path}")

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "devices": len(busy),
        "layer_s": layer_s,
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_gap_name(programs, host_spans, (a + b) / 2,
                                     window), (b - a) * 1e-9]
                          for a, b in gaps[:top]]},
    }


def _gap_name(programs, spans, t: float, window: str) -> str:
    """A gap inside a running program is the device's own; any other is
    named by what the host was doing."""
    inside = [n for n, s, e in programs if s <= t <= e]
    if inside:
        return f"inside {inside[0]}"
    return _host_name(spans, t, window)


def _host_name(spans, t: float, window: str) -> str:
    """The innermost host span covering ``t``, under the innermost span
    the benchmark itself opened (``query.*``/``stream.*``)."""
    cover = [(e - s, n) for n, s, e in spans if s <= t <= e and n != window]
    if not cover:
        return "host: no span"
    ours = sorted(c for c in cover if c[1].startswith(("query.", "stream.")))
    inner = min(cover)[1]
    if ours and ours[0][1] != inner:
        return f"{ours[0][1]} > {inner}"
    return inner


def reduce_dir(trace_dir: str, layers: dict, window: str) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return reduce_file(paths[0], layers, window)
