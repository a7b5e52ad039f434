"""Per-layer metric readers, one module per metric family.

A metric ``<family>.<suffix>`` in ``BENCHMARK.json`` is read by
``bench/metrics/<family>.py`` (the longest dotted prefix of the name that
has a module here). Each module has ``read(ctx)``, which returns a number,
or None where the run gave it nothing to read; the harness then leaves the
metric out of the result line. ``ctx`` holds ``config`` and ``traffic``
(the cell's files), ``jobs`` (the traced jobs, :class:`bench.entries.Job`),
``trace`` (:func:`bench.trace_reduce.reduce_xplane` of the traced window,
or None) and ``peaks`` (the device's row of ``peaks.json``).
"""
