"""On-chip benchmark of the served Pegasus path.

One command runs one cell of ``BENCHMARK.json`` once::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything here is the yardstick: traffic generation (``generator.py``
reading ``traffic/<mix>.json``), the configurations (``configs/<name>.json``,
with the module that makes each and its plain reference beside it), the
reduction of a profiler trace (``trace.py``), the required work per flow (``work.py``), the
table of peaks (``peaks.py``), one reader per metric (``metrics/<name>.py``)
and the comparison that decides ``correct`` (``check.py``). From the program
it takes only the system under test (``repro.launch.serve``) and its
counters.
"""
