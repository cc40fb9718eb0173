"""Tier-1 collects `benchmark/tests/test_rehearsal.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_rehearsal import *  # noqa: F401,F403
