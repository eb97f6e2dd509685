"""``chip_smoke.py``'s contract, as far as a machine without the chip can
hold it: no TPU means a non-zero exit and no result line; the parent
process stays off JAX; the script never asks for an interpreted kernel;
the compile cache is placed by one rule."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*argv, env=None, cwd=ROOT, timeout=600):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("XLA_FLAGS", None)
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run([sys.executable, *argv], env=e, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def test_without_a_chip_exits_nonzero_and_prints_no_result():
    r = _run(SMOKE)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
    for line in r.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


def test_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it must fail too."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(SMOKE).read())
    r = _run(str(lone), "--rehearse-cpu", cwd=str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_parent_never_imports_jax():
    r = _run("-c", "import sys; sys.argv = ['chip_smoke.py', '--help']\n"
                   "import chip_smoke\n"
                   "assert 'jax' not in sys.modules, 'parent imported jax'\n"
                   "assert 'paddle_infer_tpu' not in sys.modules\n")
    assert r.returncode == 0, r.stderr


def test_never_asks_for_an_interpreted_kernel():
    src = open(SMOKE).read()
    assert "interpret=True" not in src
    # the CPU rehearsal is an explicit option and labels itself
    assert "--rehearse-cpu" in src and '"rehearsal": True' in src


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_compile_cache_is_placed_by_one_rule(placed, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code (JAX reads
    the variable).  Unset: <checkout>/.jax_cache, a fixed path."""
    code = ("import json, jax\n"
            "from paddle_infer_tpu.utils.compile_cache import "
            "configure_compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "path = configure_compile_cache()\n"
            "print(json.dumps([before, path, "
            "jax.config.jax_compilation_cache_dir]))\n")
    env = {"PYTHONPATH": ROOT}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "x")
    r = _run("-c", code, env=env)
    assert r.returncode == 0, r.stderr
    before, path, after = json.loads(r.stdout.strip().splitlines()[-1])
    if placed:
        assert path == before == after == str(tmp_path / "x")
    else:
        assert path == after == os.path.join(ROOT, ".jax_cache")
