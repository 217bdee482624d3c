"""Chip benchmark of the federated round (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the chip and prints one JSON result
line.  Everything that belongs to one configuration, traffic mix or
per-layer metric is a file of its own, found by name:

- ``bench/configs/<config>.json``: the configuration as it is run;
  its ``model`` and ``data`` keys name ``bench/models/<model>.py``
  (system adapter, plain reference, work counts) and
  ``bench/datagen/<generator>.py`` (the inputs, made from the seed);
- ``bench/traffic/<traffic>.json``: cohort and execution parameters
  read by the one generator in ``bench/cohorts.py``;
- ``bench/limits/<cell>.json``: the numbers ``correct`` compares and
  their limits (``bench/compare.py``);
- ``bench/metrics/<metric>.py``: a reader with ``read(ctx)``.
"""
