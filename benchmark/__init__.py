"""The benchmark of cubed_tpu: see ``benchmark/README.md`` and ``PERF.md``."""
