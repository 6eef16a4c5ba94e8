"""Found by name from BENCHMARK.json and the data files it names; see harness/manifest.py."""
