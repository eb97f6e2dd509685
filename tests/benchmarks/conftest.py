"""The benchmark's own tests: CPU only, no chip, no topology call at
import.  ``tests/conftest.py`` has already forced eight CPU devices."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load_data(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def cpu_trace_loader(monkeypatch):
    """On the CPU the operations sit on the host plane's XLA threads: a
    test of a traced run tells ``xplane.load`` so here; the window and the
    runners have no option for it."""
    import functools

    from benchmarks import xplane

    monkeypatch.setattr(xplane, "load", functools.partial(
        xplane.load, device_prefix="/host:CPU",
        op_line="tf_XLAPjRtCpuClient"))
