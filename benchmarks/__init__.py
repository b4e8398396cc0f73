"""The benchmark (BENCHMARK.json's ``paths``): harness, generators, data files."""
