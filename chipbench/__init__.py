"""Chip benchmark of the serving engine: cells, metrics and traffic as data.

``python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on and
prints one JSON result line.  Everything that belongs to one model
configuration, one traffic mix or one metric is a file of its own, found by
the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  the configuration's sizes as run, its source,
  what was cut, its model family, and the engine geometry;
* ``families/<family>.py``   the family's parameter tree, plain reference
  and work counts (``spec.load_family`` lists what it defines);
* ``traffic/<mix>.json``     the parameters the one generator
  (:mod:`chipbench.traffic`) reads;
* ``endtoend/<metric>.py`` and ``metrics/<metric>.py``  one reader each.
"""
