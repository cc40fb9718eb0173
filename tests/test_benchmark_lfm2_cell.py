"""Tier-1 collects `benchmark/tests/test_lfm2_cell.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_lfm2_cell import *  # noqa: F401,F403
