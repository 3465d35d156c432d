"""Served-path benchmark of the fault-tolerant paged serving stack.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on and
prints one JSON result line. Everything a cell needs is found by name:

- ``bench/configs/<config>.json``: the model configuration and its engine;
- ``bench/traffic/<traffic>.json``: the parameters of one traffic mix, read
  by the generator it names, ``bench/generators/<generator>.py``;
- ``bench/cells/<workload>.json``: the limits that decide ``correct``;
- ``bench/metrics/<metric>.py``: one reader per metric.

The harness imports the program only in :mod:`bench.system`; the reference
that decides ``correct`` (:mod:`bench.reference`) imports nothing of it.
"""
