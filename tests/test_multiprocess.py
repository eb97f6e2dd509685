"""Multi-process distributed harness (round-3 verdict #6): the
reference's spawn-N-local-processes pattern (test_dist_base.py:1058
_run_cluster) — 2 real processes x 4 CPU devices rendezvous through
jax.distributed (the TCPStore analog), train DP over the 8-device global
mesh, and must match the single-process run exactly."""
import json
import os
import socket

import numpy as np
import pytest

from paddle_infer_tpu.distributed.launch import spawn
from paddle_infer_tpu.parallel import fleet, topology

import dist_worker


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    topology.set_current_mesh(None)
    fleet._state.initialized = False
    fleet._state.hcg = None
    fleet._state.strategy = None
    topology._CURRENT_HCG = None


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_dp_matches_single_process(tmp_path):
    out = str(tmp_path)
    # the multi-process run: 2 procs x 4 devices, per-process half batches
    spawn(dist_worker.dp_train_worker, (out,), nprocs=2,
          coordinator_port=_free_port())
    results = []
    for i in (0, 1):
        with open(os.path.join(out, f"proc{i}.json")) as f:
            results.append(json.load(f))
    assert results[0]["local_devices"] == 4
    # both processes observed the identical (replicated) global loss
    np.testing.assert_allclose(results[0]["losses"], results[1]["losses"],
                               rtol=1e-6)

    # single-process oracle in a subprocess (this pytest process's jax is
    # already initialized with different flags)
    import subprocess
    import sys

    code = ("import dist_worker; "
            f"dist_worker.single_process_reference({out!r})")
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(os.path.join(out, "single.json")) as f:
        single = json.load(f)
    np.testing.assert_allclose(results[0]["losses"], single["losses"],
                               rtol=1e-5)
