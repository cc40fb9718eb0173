"""Tier-1 collects `benchmark/tests/test_trace.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_trace import *  # noqa: F401,F403
