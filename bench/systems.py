"""What the traffic drives: the program as a user calls it, and the
stand-ins that prove the comparison can fail.

``ProgramQueries`` and ``ProgramStreams`` are the system under test:
queries called as ``queries/<name>.py`` calls the program (the suite's
``SuiteQuery.run`` with its defaults), and standing
``repro.core.PruneStream``s. With each query module's ``program`` they
are the only code here that imports the program.

``ControlQueries`` and ``ControlStreams`` put the plain reference in the
program's place, computed in bfloat16 (the configuration states float32
values and exact integer sums). ``FAULTS`` break the program's timed path
in the ways a cell can be broken; the rehearsal test drives them.
"""
from __future__ import annotations

import numpy as np

from bench import reference
from bench.loops import clock
from bench.queries import BF16, bound


# ----------------------------------------------------------- the program
class ProgramQueries:
    """Every held column on the device, and each query's tables under
    the program's column names (``BIND``), as ``queries/<name>.py``
    calls the program."""

    def __init__(self, tables: dict, names):
        import jax

        from repro.query import Table

        held = {t: {c: jax.device_put(v) for c, v in cols.items()}
                for t, cols in tables.items()}
        jax.block_until_ready(held)
        self.views = {
            name: {t: Table(t, cols) for t, cols in
                   bound(held, reference.query(name).BIND).items()}
            for name in names}

    def run(self, name: str):
        return reference.query(name).program(self.views[name])

    def describe(self) -> str:
        from repro.core import MEASURED_MERGE_COSTS

        return f"merge costs the planner measured: {MEASURED_MERGE_COSTS}"


class ProgramStreams:
    """One standing ``PruneStream`` per stream of the mix, each fed the
    same micro-batches (its own column of them)."""

    def __init__(self, specs):
        from repro.core import PruneStream

        self.names = [s["name"] for s in specs]
        self.columns = [s["column"] for s in specs]
        self.streams = [PruneStream(s["algo"], shards=s["shards"],
                                    retain=False, **s["params"])
                        for s in specs]

    def fold(self, batch: dict):
        """Fold one micro-batch into every stream; returns the live
        masks (device arrays, copying to the host) and each fold call's
        host seconds."""
        masks, took = [], []
        for stream, col in zip(self.streams, self.columns):
            a = clock()
            idx = stream.fold(batch[col])
            took.append(clock() - a)
            m = stream.live_mask(idx)
            m.copy_to_host_async()
            masks.append(m)
        return masks, took

    def merged_everywhere(self) -> bool:
        return all(s.stats["merges"] > 0 for s in self.streams)

    def reset(self) -> None:
        for s in self.streams:
            s.reset()

    def describe(self) -> str:
        return "; ".join(f"{n}: {s.stats}"
                         for n, s in zip(self.names, self.streams))


# ------------------------------------------------------------ controls
class ControlQueries:
    """Each query's reference, computed in bfloat16, in the program's
    place."""

    def __init__(self, cfg: dict, tables: dict):
        self.q, self.tables = cfg["queries"], tables

    def run(self, name: str):
        return reference.query(name).answer(self.q, self.tables, BF16)


class ControlStreams:
    """Each stream's exact forwarding rule, computed in bfloat16
    (``streams/<algo>.py``), in the program's place."""

    def __init__(self, specs):
        self.names = [s["name"] for s in specs]
        self.columns = [s["column"] for s in specs]
        self.specs = specs
        self.reset()

    def reset(self) -> None:
        self.rules = [reference.stream_algo(s["algo"]).Control(s["params"])
                      for s in self.specs]

    def fold(self, batch: dict):
        return ([r.keep(batch[c]) for r, c in zip(self.rules, self.columns)],
                [0.0] * len(self.rules))

    def merged_everywhere(self) -> bool:
        return True


# -------------------------------------------------------------- faults
class _AlterAnswer:
    """Queries: the first answer is altered where it is produced.
    Streams: the first batch's live mask drops its largest entry."""

    def __init__(self, inner):
        self.inner = inner
        self.names = getattr(inner, "names", None)
        self.done = False

    def run(self, name: str):
        ans = self.inner.run(name)
        if not self.done:
            self.done = True
            ans = _alter(ans)
        return ans

    def fold(self, batch: dict):
        masks, took = self.inner.fold(batch)
        if not self.done:
            self.done = True
            col = self.inner.columns[0]
            m = np.array(masks[0])
            m[int(np.argmax(batch[col]))] = False
            masks = [m] + list(masks[1:])
        return masks, took

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _alter(ans):
    if isinstance(ans, dict):
        k = next(iter(ans))
        return {**ans, k: ans[k] + 1}
    if isinstance(ans, list):
        (k, v), rest = ans[0], ans[1:]
        return [(k, v + 1)] + rest
    return ans + 1


class _HalfBatch:
    """Queries: every table is cut to its first half of rows.
    Streams: each micro-batch's second half is never folded (pruned)."""

    def __init__(self, inner):
        self.inner = inner
        self.names = getattr(inner, "names", None)
        if isinstance(inner, ProgramQueries):
            from repro.query import Table

            inner.views = {
                q: {n: Table(n, {c: v[: v.shape[0] // 2]
                                 for c, v in t.cols.items()})
                    for n, t in view.items()}
                for q, view in inner.views.items()}

    def run(self, name: str):
        return self.inner.run(name)

    def fold(self, batch: dict):
        h = len(next(iter(batch.values()))) // 2
        half = {c: v[:h] for c, v in batch.items()}
        masks, took = self.inner.fold(half)
        return [np.concatenate([np.asarray(m), np.zeros(h, bool)])
                for m in masks], took

    def __getattr__(self, name):
        return getattr(self.inner, name)


FAULTS = {"answer_altered": _AlterAnswer, "half_batch": _HalfBatch}
