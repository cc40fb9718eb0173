"""Tier-1 collects `benchmark/tests/test_loadgen.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_loadgen import *  # noqa: F401,F403
