"""One module per ``PruneStream`` algorithm an open-loop mix can run,
found by the algorithm's name.

Each has ``wrong(params, values, keep)``: how many entries the answer
completed from the entries ``keep`` forwarded differs by from the exact
answer over all ``values`` (plain NumPy); and ``Control(params)``, whose
``keep(values)`` is the exact forwarding rule computed in bfloat16: the
control that stands in for the program.
"""
import ml_dtypes

BF16 = ml_dtypes.bfloat16
