"""The benchmark of bluefog-tpu: one command, cells named in
``BENCHMARK.json``, every configuration, traffic mix and per-layer
metric a file of its own.  See ``PERF.md``."""
