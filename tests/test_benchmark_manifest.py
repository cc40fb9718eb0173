"""Tier-1 collects `benchmark/tests/test_manifest.py` here (the driver runs `pytest tests/`)."""
from benchmark.tests.test_manifest import *  # noqa: F401,F403
