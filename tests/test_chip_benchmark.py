"""Tier-1 collects the chip benchmark's own cases from here; they live with
the benchmark, in benchmarks/chip/test_chip_benchmark.py."""
from benchmarks.chip.test_chip_benchmark import *  # noqa: F401,F403
