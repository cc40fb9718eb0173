"""The driver's tier-1 command is `pytest tests/`, and `benchmark/` is the
yardstick no program PR may edit: each `benchmark/tests/test_<name>.py`
is collected through `tests/test_benchmark_<name>.py`, one file each so
that `--dist loadfile` spreads the rehearsals over the workers."""

import glob
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_benchmark_test_module_is_collected_by_tier1():
    modules = sorted(glob.glob(os.path.join(ROOT, "benchmark", "tests", "test_*.py")))
    assert modules, "benchmark/tests holds no test module"
    for path in modules:
        name = os.path.basename(path)[:-3]
        collector = os.path.join(ROOT, "tests", f"test_benchmark_{name[5:]}.py")
        assert os.path.exists(collector), f"nothing under tests/ collects {name}"
        with open(collector) as f:
            assert f"from benchmark.tests.{name} import *" in f.read(), collector
