"""The chip benchmark: BENCHMARK.json's cells, their data and yardstick."""
