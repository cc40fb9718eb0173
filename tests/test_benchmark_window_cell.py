"""Tier-1 collects `benchmark/tests/test_window_cell.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_window_cell import *  # noqa: F401,F403
