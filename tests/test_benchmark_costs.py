"""Tier-1 collects `benchmark/tests/test_costs.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_costs import *  # noqa: F401,F403
