"""The benchmark's general machinery: the loop, the clock, the span recorder,
the reduction of the device trace, the peaks table and the manifest check.

Nothing in this package names a cell, a configuration, a query, a traffic mix
or a per-layer metric: each is a file of its own under ``benchmark/``, found
by the name that ``BENCHMARK.json`` gives it (see ``benchmark/README.md``)."""
