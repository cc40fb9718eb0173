"""Tier-1 collects `benchmark/tests/test_backend.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_backend import *  # noqa: F401,F403
