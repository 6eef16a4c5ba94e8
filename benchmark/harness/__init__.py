"""Window, statistics, trace reduction, peaks and printer. Nothing here names
a configuration, a traffic mix or a metric: those come from ``BENCHMARK.json``
and the files it names."""
