"""Per-layer metric readers, one module per metric name in
``BENCHMARK.json``; each defines ``read(run) -> float | None``."""
