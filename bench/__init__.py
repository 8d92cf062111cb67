"""On-chip benchmark of the ColD Fusion service.

One command runs one cell of ``BENCHMARK.json`` once::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that defines a cell is data found by name: ``configs/<config>.json``
(the model as it is run), ``traffic/<traffic>.json`` (the mix, read by the
driver its ``kind`` names in ``drivers/``) and ``metrics/<metric>.py`` (one
reader per per-layer metric).  The yardstick lives here too, out of reach of
the program: the peak table (``peaks.json``), the byte and FLOP counts
(``counts.py``), the trace reduction (``tracing.py``) and the float32
reference math (``reference/``).
"""
