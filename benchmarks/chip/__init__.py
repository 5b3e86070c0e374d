"""On-chip benchmark of the paper's training and screening paths.

``run.py`` runs one cell of ``BENCHMARK.json`` (a configuration under a
traffic mix) on the chips of the machine it starts on and prints one JSON
line.  Everything a cell needs is found by name: ``configs/<config>.json``,
``families/<family>.py`` (the program's model and the plain reference),
``mixes/<mix>.json`` naming a driver in ``drivers/<driver>.py``,
``limits/<cell>.json`` for the correctness limits, and
``metrics/<metric>.py`` for each per-layer metric.  Adding a cell, a
configuration or a metric adds files here and edits none.
"""
