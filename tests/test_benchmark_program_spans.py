"""Tier-1 collects `benchmark/tests/test_program_spans.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_program_spans import *  # noqa: F401,F403
