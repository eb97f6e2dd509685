"""Test env: force an 8-device virtual CPU mesh BEFORE jax initializes,
mirroring the reference's gloo-only CPU path for testing collective logic
without accelerators (test_dist_base.py:1316 _run_cluster_gloo)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the tier-1 "
        "gate (-m 'not slow')")
    config.addinivalue_line(
        "markers", "lockcheck: spawns an instrumented-lock subprocess "
        "run of the serving suites (see analysis/lockcheck.py)")


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_session():
    """PIT_LOCKCHECK=1 wraps the whole session in the runtime lock
    checker: serving-plane locks constructed during the run are
    instrumented, and at session end the run FAILS on any lock-order
    inversion / self-deadlock / host-sync-under-lock, or on any
    observed edge missing from the committed static lock graph
    (tools/lock_graph_baseline.json) — dynamic must be a subset of
    static, else the analyzer has a blind spot."""
    if os.environ.get("PIT_LOCKCHECK") != "1":
        yield
        return
    import json

    from paddle_infer_tpu.analysis.lockcheck import instrument_locks

    with instrument_locks() as chk:
        yield
    assert chk.violations == [], (
        f"lockcheck violations: {json.dumps(chk.violations, indent=2)}")
    base = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "tools", "lock_graph_baseline.json")
    with open(base) as f:
        static = json.load(f)
    gaps = chk.gap_report(static)
    assert gaps == [], (
        f"dynamic lock edges missing from the static graph: {gaps}")


@pytest.fixture(scope="session", autouse=True)
def _warm_the_traced_matmul():
    """``tests/benchmarks/test_bench_xplane.py`` gives a profiler trace
    0.2 s in which ``jnp.ones((256, 256))`` and its product with itself
    must run.  Compiled inside that window on a loaded machine (the gate's
    six workers) the two take 0.2-0.4 s and the trace closes without its
    ``dot``: one run in five here under load, on the parent as on any
    tree.  A process that has compiled the two shapes runs them in a
    millisecond, so every worker compiles them once, here; mending the
    test is a ``benchmark`` PR's (ROADMAP D12 (l))."""
    import jax.numpy as jnp

    x = jnp.ones((256, 256))
    (x @ x).block_until_ready()
    yield


@pytest.fixture(autouse=True)
def _seed():
    import paddle_infer_tpu as pit

    np.random.seed(0)
    pit.seed(0)
    yield
