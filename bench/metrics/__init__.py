"""Per-layer metrics, one module each, named as in ``BENCHMARK.json``.

Each has ``read(ctx) -> float | None``; ``None`` when the traced run gave
it nothing to read, and the harness then leaves the metric out.  ``ctx``
carries the configuration (``hp``), the chip's ``peaks``, the reduced
trace (``trace``, with its window ``lo``/``hi`` in trace nanoseconds),
the benchmark's host ``spans`` (with ``host_window``, the traced part on
the host clock, and ``window``, the whole measured window), and the
engine's counter deltas over the window (``counters``).
"""
