"""Tests of the benchmark's harness, reference and data."""
