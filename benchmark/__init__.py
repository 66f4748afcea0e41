"""The benchmark: yardstick, traffic, references and harness (BENCHMARK.json)."""
