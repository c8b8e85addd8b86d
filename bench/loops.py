"""The one traffic generator: it reads a mix from ``traffic/<mix>.json``
and drives a system under test with it.

``"loop": "closed"``: one client sends the mix's queries in turn, each
as soon as the previous answer is on the host. The window runs until
the query in flight at the deadline has finished, so a long query is
counted whole and the rate is not quantised by it.

``"loop": "open"``: events, the rows of the configuration's ``table``
in the order its schema generates them (``schemas/<schema>.py``'s
``make_stream``), arrive at ``rate_per_s`` on a fixed schedule
that does not slow when the system does. They are cut into micro-batches
of ``micro_batch`` events; a batch is folded when its last event is due.
An event's latency runs from its own due time until every stream's keep
decisions for its batch are on the host.
"""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

clock = time.perf_counter


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole-number seed, negative or past 2**63."""
    words = [(seed >> (32 * i)) & 0xFFFFFFFF for i in range(3)]
    return np.random.default_rng(words + [1 if seed < 0 else 0, stream])


def no_span(name: str):
    return contextlib.nullcontext()


# ------------------------------------------------------------ closed loop
def closed_loop(system, names, *, seconds=None, until=None, span=no_span):
    """Run ``names`` in turn for ``seconds``, or until the event ``until``
    is set; the query in flight then finishes.

    Returns ``{"kind": "closed", "start", "end", "queries": [(name,
    start, end)], "answers": [(name, answer)]}``, times on ``clock``.
    """
    queries, answers = [], []
    start = clock()
    deadline = start + seconds if until is None else float("inf")
    i = 0
    while clock() < deadline and not (until is not None and until.is_set()):
        name = names[i % len(names)]
        a = clock()
        with span(f"query.{name}"):
            ans = system.run(name)
        b = clock()
        queries.append((name, a, b))
        answers.append((name, ans))
        i += 1
    end = queries[-1][2] if queries else clock()
    return {"kind": "closed", "start": start, "end": end,
            "queries": queries, "answers": answers}


def closed_loop_cut(system, names, *, lead_s, cut_s, start, stop, span):
    """The mix's queries in turn on a worker thread, from ``lead_s``
    before a traced slice until the slice has ended; this thread traces
    the slice ``[lead_s, lead_s + cut_s]``, as the span ``bench.window``,
    and stops the trace before the query in flight finishes. For queries
    whose whole run the profiler's buffer cannot hold: a query longer
    than ``lead_s + cut_s`` is the only one, and the slice is a part of
    it; shorter ones fill the slice whole, but for its two ends."""
    out, err = {}, []
    ended = threading.Event()

    def work():
        try:
            out.update(closed_loop(system, names, until=ended, span=span))
        except BaseException as e:  # re-raised on the calling thread
            err.append(e)
            raise

    worker = threading.Thread(target=work)
    worker.start()
    time.sleep(lead_s)
    start()
    try:
        with span("bench.window"):
            time.sleep(cut_s)
    finally:
        ended.set()
        stop()
        worker.join()
    if err:
        raise err[0]
    return dict(out, cut_s=cut_s)


# -------------------------------------------------------------- open loop
def _ready(x) -> bool:
    return x.is_ready() if hasattr(x, "is_ready") else True


def open_loop(system, traffic: dict, events: dict, *, seconds: float,
              span=no_span):
    """Offer ``events`` at the mix's rate for ``seconds``.

    Returns ``{"kind": "open", ...}`` with per-event latencies, the
    events delivered inside the window, the host time of every fold
    call, how late each batch was folded after its due time, and the
    keep masks in arrival order (one bool array per stream).
    """
    rate = float(traffic["rate_per_s"])
    b = int(traffic["micro_batch"])
    nb = min(int(rate * seconds) // b, len(next(iter(events.values()))) // b)
    due = [((k + 1) * b - 1) / rate for k in range(nb)]
    masks = [[] for _ in system.names]
    done = np.zeros(nb)
    lateness = np.zeros(nb)
    fold_s: list = []
    inflight: list = []
    k = 0
    t0 = clock()
    while k < nb or inflight:
        delivered = False
        while inflight and all(_ready(m) for m in inflight[0][1]):
            j, out = inflight.pop(0)
            with span("stream.poll"):
                for s, m in enumerate(out):
                    masks[s].append(np.asarray(m))
            done[j] = clock() - t0
            delivered = True
        now = clock() - t0
        if k < nb and now >= due[k]:
            lateness[k] = now - due[k]
            batch = {c: v[k * b:(k + 1) * b] for c, v in events.items()}
            with span("stream.fold"):
                out, took = system.fold(batch)
            fold_s.extend(took)
            inflight.append((k, out))
            k += 1
        elif not delivered:
            wait = (due[k] - now) if k < nb else 1e-4
            time.sleep(min(max(wait, 0.0), 1e-4))
    ends = np.repeat(done, b)
    dues = np.arange(nb * b) / rate
    return {
        "kind": "open", "seconds": float(seconds), "rate": rate,
        "offered": nb * b,
        "delivered": int((done <= seconds).sum()) * b,
        "latency_s": (ends - dues).astype(np.float32),
        "fold_s": np.asarray(fold_s), "lateness_s": lateness,
        "masks": [np.concatenate(m) if m else np.zeros(0, bool)
                  for m in masks],
    }
