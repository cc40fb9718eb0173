"""Tier-1 collects `benchmark/tests/test_window_account.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_window_account import *  # noqa: F401,F403
