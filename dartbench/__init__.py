"""Chip benchmark of the DART runtime: one-sided latency, message rate and
bandwidth, driven by the data files beside this package.

``python3 dartbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything a
cell needs is found by name: its deployment in ``configs/<config>.json``,
its traffic mix in ``traffic/<mix>.json`` (read by :mod:`.generator`) and
each metric's reader in ``metrics/<metric>.py``.
"""
