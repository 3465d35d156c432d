"""Served-path benchmark of the fault-tolerant paged serving stack.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on and
prints one JSON result line. Everything a cell needs is found by name:

- ``bench/configs/<config>.json``: the model configuration and its engine;
- ``bench/arch/<model_type>.py``: all the harness knows of the
  configuration's architecture: its mapping to the program, its weights,
  its reference forward, its layers' attention and its FLOPs per token;
- ``bench/traffic/<traffic>.json``: the parameters of one traffic mix, read
  by the generator it names, ``bench/generators/<generator>.py``;
- ``bench/cells/<workload>.json``: the limits that decide ``correct``;
- ``bench/metrics/<metric>.py``: one reader per metric.

The harness imports the program only in :mod:`bench.system` and in the
architecture modules' ``model_config``; the reference forward that decides
``correct`` (each architecture module's ``logits``, at the precision
:mod:`bench.reference` sets) imports nothing of it.

A configuration of a new architecture is new files alone: its
``bench/arch/<model_type>.py``, ``bench/configs/<name>.json``, a traffic
file where it needs one, ``bench/cells/<cell>.json``, and its entries in
``BENCHMARK.json``.
"""
