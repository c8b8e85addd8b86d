"""The chip benchmark of the Cheetah query path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of the root ``BENCHMARK.json`` once and
prints one JSON result line. Everything a cell needs is found by name:

* ``configs/<config>.json``: a deployment (sizes, source, guarantee,
  cuts); its ``schema`` names ``schemas/<schema>.py``, which makes the
  tables, and an open loop's rows, from the seed.
* ``queries/<query>.py``: one query of a closed loop: the program's
  call, its column bindings and the plain NumPy reference;
  ``streams/<algo>.py``: the same for one stream algorithm.
* ``traffic/<mix>.json``: the mix, read by the one generator in
  ``loops.py`` (closed-loop queries or an open-loop event stream).
* ``metrics/<metric>.py``: one reader per metric, end to end or per
  layer; ``read(ctx)`` returns a number or ``None``.
* ``layers/<layer>.json``: which XLA modules belong to one layer.
* ``peaks/<kind>.json``: one device's peaks, keyed by its ``device_kind``.
"""
