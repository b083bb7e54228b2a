"""The benchmark: BENCHMARK.json's command, its data files and its yardstick."""
