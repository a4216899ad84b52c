"""On-chip benchmark of the serving path: one command runs one cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that defines a cell is data found by name: a model
configuration in ``configs/<config>.json``, a traffic mix in
``traffic/<mix>.json`` (read by the generator module it names), each
per-layer metric in ``metrics/<metric>.py`` and each cell's correctness
limit in ``limits/<workload>.json``.  The yardstick (traffic generation,
weights, the float32 reference, the comparison, the peak table, the
trace reduction and the metric arithmetic) lives here and imports
nothing of the program but the serving engine it drives.
"""
