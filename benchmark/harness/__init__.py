"""Shared harness of the benchmark: knows no cell, configuration or metric
by name — those are files found by the names ``BENCHMARK.json`` gives."""
