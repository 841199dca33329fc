"""One reader per metric: ``<name>.py`` defines ``read(ctx)``, which
returns the number, or ``None`` where this run has nothing to read (the
harness then leaves the metric out). ``ctx`` is built by ``bench/run.py``:
per-request records, the server's counters before and after the window,
the reduced trace of the traced slice, the configuration's geometry and
the chip's peaks."""
