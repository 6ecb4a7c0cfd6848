"""Benchmark harness: see run.py."""
