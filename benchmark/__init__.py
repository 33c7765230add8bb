"""Chip benchmark of the served BatchCheck path.

``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix or per-layer metric is found by
name: ``configs/<name>.json`` with ``generators/<generator>.py``,
``traffic/<name>.json`` with ``loops/<loop>.py``, and
``metrics/<metric>.py``.
"""
