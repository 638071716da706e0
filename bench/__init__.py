"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
from the root of a checkout.  Everything that belongs to one configuration,
one traffic mix or one metric is a file of its own, found by its name:
``configs/<config>.json``, ``traffic/<mix>.json`` (whose ``kind`` names
``traffic_kinds/<kind>.py``), ``metrics/<metric>.py``, and, per model
family, ``adapters/<family>.py`` (the program side) and
``reference/<family>.py`` (the plain float32 reference).
"""
