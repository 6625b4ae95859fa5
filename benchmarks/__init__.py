"""The benchmark: BENCHMARK.json's command and everything it measures with."""
