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
* spans: the host spans of the thread that ran the window's queries or
  folds (the harness's ``query.*``/``stream.*`` spans and the program's
  own inside them), clipped to the window. That is the window's own
  thread, unless only a worker thread ran them (a slice traced while a
  worker runs the mix, ``loops.closed_loop_cut``). A span the thread
  opened before the trace started is not recorded.
* gaps: each device's idle intervals inside the window, and for each
  whether a device program was running over it (a gap between the
  operations of one program, such as the steps of a ``lax.scan``, is the
  device's own and not the host's).
* breakdown: the programs that took most device time (operations, where
  no program began inside the window), and the longest idle gaps, each
  named ``inside <program>`` or by the innermost host span that covers
  it.

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
OURS = ("query.", "stream.")   # the harness's spans around each call


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


def _query_thread(lines, window: str, lo: float, hi: float):
    """The events of the host thread that ran the window's queries or
    folds: the one that opened the window, unless only another thread
    opened the harness's spans inside it."""
    own = next(ln for ln in lines if window in ln[0])

    def ran(line):
        n, s, e = line
        return any(x.startswith(OURS) and a < hi and b > lo
                   for x, a, b in zip(n, s, e))

    return next((ln for ln in [own, *lines] if ran(ln)), own)


def _inside(t, starts, ends) -> np.ndarray:
    """For each time in ``t``, whether one of the intervals covers it."""
    if starts.size == 0:
        return np.zeros(len(t), bool)
    us, ue = _union(starts, ends)
    i = np.searchsorted(us, t, side="right") - 1
    return (i >= 0) & (t <= ue[np.maximum(i, 0)])


def reduce_file(path: str, layers: dict, window: str, top: int = 10) -> dict:
    """The window's busy, layer and breakdown numbers, and its ``spans``
    (``[(name, start_s, end_s)]``), ``gaps`` (per device, ``[(start_s,
    end_s)]``) and ``gap_in_program`` (per device, one bool a gap), in
    seconds from the window's start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host = None               # the lines of the host plane with the window
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
        elif plane.name.startswith("/host:") and host is None:
            lines = [_events(ln) for ln in plane.lines]
            if any(window in n for n, _, _ in lines):
                host = lines
    if host is None:
        raise ValueError(f"no host span {window!r} in {path}")
    lo, hi = next((s, e) for ln in host for n, s, e in zip(*ln)
                  if n == window)
    host_spans = list(zip(*_query_thread(host, window, lo, hi)))

    busy, gaps, in_prog, by_name, programs = [], [], [], {}, []
    layer_s = dict.fromkeys(layers, 0.0)
    pats = {k: [re.compile(p) for p in v] for k, v in layers.items()}
    for (names, s, e), mods in devices:
        cs, ce = np.clip(s, lo, hi), np.clip(e, lo, hi)
        inside = ce > cs
        if not inside.any():
            continue
        us, ue = _union(cs[inside], ce[inside])
        busy.append(float((ue - us).sum()) * 1e-9)
        ga, gb = np.concatenate([[lo], ue]), np.concatenate([us, [hi]])
        keep = gb > ga
        ga, gb = ga[keep], gb[keep]
        gaps.append((ga, gb))
        in_prog.append(_inside((ga + gb) / 2, mods[1], mods[2]))
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

    longest = sorted(((a, b) for ga, gb in gaps for a, b in zip(ga, gb)),
                     key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "devices": len(busy),
        "layer_s": layer_s,
        "spans": [(n, (max(s, lo) - lo) * 1e-9, (min(e, hi) - lo) * 1e-9)
                  for n, s, e in host_spans
                  if n != window and min(e, hi) > max(s, lo)],
        "gaps": [list(zip(((ga - lo) * 1e-9).tolist(),
                          ((gb - lo) * 1e-9).tolist())) for ga, gb in gaps],
        "gap_in_program": [m.tolist() for m in in_prog],
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_gap_name(programs, host_spans, (a + b) / 2,
                                     window), (b - a) * 1e-9]
                          for a, b in longest]},
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
    ours = sorted(c for c in cover if c[1].startswith(OURS))
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
