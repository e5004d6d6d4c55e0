"""Per-layer metric readers, one module per quantity.

A metric ``<m>`` or ``<m>.<suffix>`` in ``BENCHMARK.json`` is read by
``<m>.py``'s ``read(run) -> float | None`` from a :class:`bench.lib.
record.Run`.  ``None`` means there was nothing to read, and the metric
is left out of the result line; a share of a roofline or a peak is
never reported as 0 for want of data.
"""
