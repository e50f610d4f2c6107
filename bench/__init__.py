"""Chip benchmark of the tenant-fleet RFF-KLMS/KRLS server.

One command, ``python3 bench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>``, runs one cell of ``BENCHMARK.json``: a deployment
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``), with per-layer metrics read by
``bench/metrics/<metric>.py``. Everything that measures (the traffic
generator, the plain references, the trace reduction, the operation and
byte counts, the table of peaks) lives here, apart from the program.
"""
