"""One module per query a closed-loop mix can send, found by its name.

Each has ``BIND``: for each table the query reads, the program's column
names mapped to the configuration's held columns (the program's suite
names its own columns; ``BIND`` says which TPC-H column each one is);
``program(tables)``: the call a user makes, over the program's tables
on the device under the suite's names; and ``answer(q, tables,
dtype=None)``: the plain NumPy reference over the run's NumPy tables
under the held names, with the configuration's query constants ``q``,
in the form ``program`` returns. With ``dtype=BF16`` the same answer is
computed in bfloat16: the control that stands in for the program to
show that the comparison fails a lower precision than the configuration
states.
"""
import ml_dtypes

BF16 = ml_dtypes.bfloat16


def bound(tables: dict, bind: dict) -> dict:
    """{table: {program column: held column's array}} for one query."""
    return {t: {c: tables[t][held] for c, held in cols.items()}
            for t, cols in bind.items()}
