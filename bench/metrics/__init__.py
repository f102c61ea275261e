"""Per-layer metric readers, one module per metric, found by the metric's
name in BENCHMARK.json (dots become underscores): ``read(r) -> float |
None``, where ``r`` holds the trace reduction (``r["trace"]``), the
driver's inputs for the traced steps (``r["inputs"]``), the chip's peaks
(``r["peaks"]``) and the number of chips (``r["chips"]``).  A reader that
finds nothing to read returns None, and the metric is left out."""
