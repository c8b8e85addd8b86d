"""The comparison that decides ``correct``.

Queries: every answer the window produced is compared with the plain
reference of its query (``queries/<name>.py``) on the same tables.
Streams: each stream's answer is completed from the entries its live
masks kept, as the master would, and compared with the exact answer over
every event offered (``streams/<algo>.py``). Every number compared is a
count of wrong answers or wrong entries; the configuration states exact
answers, so every limit is 0.
"""
from __future__ import annotations

import importlib


def query(name: str):
    return importlib.import_module("bench.queries." + name)


def stream_algo(name: str):
    return importlib.import_module("bench.streams." + name)


def check_queries(cfg: dict, tables: dict, answers) -> tuple:
    """({"<query>_wrong": {"value", "limit"}}, attempted, failed)."""
    want = {name: query(name).answer(cfg["queries"], tables)
            for name in dict.fromkeys(n for n, _ in answers)}
    wrong = dict.fromkeys(want, 0)
    for name, ans in answers:
        wrong[name] += int(ans != want[name])
    compared = {f"{n}_wrong": {"value": w, "limit": 0}
                for n, w in wrong.items()}
    return compared, len(answers), sum(wrong.values())


def check_streams(traffic: dict, events: dict, window: dict) -> tuple:
    """({"<stream>_wrong": {"value", "limit"}}, attempted, failed)."""
    n = window["offered"]
    compared = {}
    for spec, keep in zip(traffic["streams"], window["masks"]):
        values = events[spec["column"]][:n]
        wrong = (stream_algo(spec["algo"]).wrong(spec["params"], values, keep)
                 if keep.shape[0] == n else n)
        compared[f"{spec['name']}_wrong"] = {"value": wrong, "limit": 0}
    # late decisions are late, not failed: only missing ones fail
    failed = n - min((k.shape[0] for k in window["masks"]), default=0)
    return compared, n, int(failed)


def work(name: str, tables: dict) -> dict:
    """Rows of every table the query reads, summed, and the bytes of the
    held columns it has to read once."""
    bind = query(name).BIND
    return {"rows": sum(len(next(iter(tables[t].values()))) for t in bind),
            "bytes": sum(int(tables[t][c].nbytes)
                         for t, cols in bind.items() for c in cols.values())}
