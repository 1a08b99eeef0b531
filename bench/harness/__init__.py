"""Benchmark harness: traffic generation, the served-path stepper, trace
reduction, FLOP counts, the peaks table and the correctness comparison.
Per-configuration, per-traffic and per-metric data live beside it in
``bench/configs``, ``bench/traffic`` and ``bench/metrics``."""
