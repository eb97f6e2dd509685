"""save_pretrained / from_pretrained (PaddleNLP PretrainedModel surface;
weights through the native mmap TensorStore)."""
import os
import sys

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.core.tensor import Tensor
from paddle_infer_tpu.models import (GPTConfig, GPTForCausalLM,
                                     LlamaConfig, LlamaForCausalLM)


def _tiny_gpt():
    pit.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))


def test_roundtrip_identical_outputs(tmp_path):
    m = _tiny_gpt()
    m.eval()
    d = str(tmp_path / "gpt")
    m.save_pretrained(d)
    assert os.path.exists(os.path.join(d, "config.json"))
    m2 = GPTForCausalLM.from_pretrained(d)
    ids = np.random.RandomState(0).randint(0, 96, (2, 8)).astype(np.int32)
    np.testing.assert_allclose(m(Tensor(ids)).numpy(),
                               m2(Tensor(ids)).numpy(), atol=1e-6)


def test_config_preserved_and_arch_checked(tmp_path):
    pit.seed(1)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=64, max_position_embeddings=64))
    d = str(tmp_path / "llama")
    m.save_pretrained(d)
    m2 = LlamaForCausalLM.from_pretrained(d)
    assert m2.config.num_key_value_heads == 2
    assert m2.config.rope_theta == m.config.rope_theta
    with pytest.raises(ValueError, match="holds a LlamaForCausalLM"):
        GPTForCausalLM.from_pretrained(d)


def test_loaded_model_generates(tmp_path):
    m = _tiny_gpt()
    m.eval()
    ids = np.random.RandomState(1).randint(0, 96,
                                           (1, 6)).astype(np.int32)
    want = m.generate(ids, max_new_tokens=4)
    d = str(tmp_path / "gpt2")
    m.save_pretrained(d)
    m2 = GPTForCausalLM.from_pretrained(d)
    got = m2.generate(ids, max_new_tokens=4)
    np.testing.assert_array_equal(want, got)


def test_ernie_heads_roundtrip(tmp_path):
    from paddle_infer_tpu.models import (ErnieConfig,
                                         ErnieForSequenceClassification)

    pit.seed(2)
    cfg = ErnieConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=4, intermediate_size=64,
                      max_position_embeddings=32, type_vocab_size=2,
                      hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    m = ErnieForSequenceClassification(cfg, num_classes=5)
    m.eval()
    d = str(tmp_path / "ernie")
    m.save_pretrained(d)
    m2 = ErnieForSequenceClassification.from_pretrained(d)
    assert m2.classifier.weight.shape[-1] == 5
    ids = np.random.RandomState(0).randint(0, 128,
                                           (2, 8)).astype(np.int32)
    np.testing.assert_allclose(m(Tensor(ids)).numpy(),
                               m2(Tensor(ids)).numpy(), atol=1e-6)


def test_automodel_dispatch(tmp_path):
    from paddle_infer_tpu.models import AutoConfig, AutoModel

    m = _tiny_gpt()
    m.eval()
    d = str(tmp_path / "auto")
    m.save_pretrained(d)
    m2 = AutoModel.from_pretrained(d)
    assert type(m2).__name__ == "GPTForCausalLM"
    ids = np.random.RandomState(3).randint(0, 96, (1, 6)).astype(np.int32)
    np.testing.assert_allclose(m(Tensor(ids)).numpy(),
                               m2(Tensor(ids)).numpy(), atol=1e-6)
    cfg = AutoConfig.from_pretrained(d)
    assert cfg.hidden_size == 32


def test_launch_cli_args(tmp_path):
    import subprocess
    import sys

    script = tmp_path / "job.py"
    script.write_text(
        "import os, sys\n"
        "print('ARGS', sys.argv[1:])\n"
        "print('JOB', os.environ.get('PTI_JOB_ID'))\n"
        "print('ADDR', os.environ.get('PTI_COORDINATOR_ADDR'))\n")
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    r = subprocess.run(
        [sys.executable, "-m", "paddle_infer_tpu.distributed.launch",
         "--master", "127.0.0.1:7777", "--nnodes", "2", "--rank", "1",
         "--job_id", "j1", str(script), "--lr", "0.1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-400:]
    assert "ARGS ['--lr', '0.1']" in r.stdout
    assert "JOB j1" in r.stdout
    assert "ADDR 127.0.0.1:7777" in r.stdout


def test_launch_multihost_env_wiring(tmp_path):
    """--master + --nproc_per_node must form ONE global job: world size
    nnodes*nproc, ranks offset by node rank (review fix)."""
    import subprocess

    script = tmp_path / "job.py"
    # each worker records its env in its own file (two children share a
    # stdout pipe — concurrent prints can interleave mid-line)
    script.write_text(
        "import os, sys\n"
        "r = os.environ.get('PTI_PROCESS_ID')\n"
        "open(os.path.join(os.path.dirname(os.path.abspath(__file__)),\n"
        "     f'env.{r}'), 'w').write(\n"
        "    f\"W {os.environ.get('PTI_NUM_PROCESSES')} \"\n"
        "    f\"A {os.environ.get('PTI_COORDINATOR_ADDR')}\")\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    r = subprocess.run(
        [sys.executable, "-m", "paddle_infer_tpu.distributed.launch",
         "--master", "10.0.0.1:9999", "--nnodes", "2", "--rank", "1",
         "--nproc_per_node", "2", str(script)],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-400:]
    ranks = sorted(f.name.split(".")[1] for f in tmp_path.glob("env.*"))
    assert ranks == ["2", "3"], ranks     # node rank 1 -> global 2, 3
    for rank in ranks:
        assert (tmp_path / f"env.{rank}").read_text() == \
            "W 4 A 10.0.0.1:9999"


def test_bf16_checkpoint_loads_as_bf16_without_an_init_copy(tmp_path,
                                                            monkeypatch):
    """The loaded model has the checkpoint's dtype, and no initializer
    ran to build it: a default-initialised fp32 copy of a model that is
    about to be overwritten is twice the HBM of a bf16 checkpoint."""
    import jax.numpy as jnp

    m = _tiny_gpt()
    m.bfloat16()
    d = str(tmp_path / "gpt_bf16")
    m.save_pretrained(d)

    import jax

    def no_bits(*a, **kw):
        raise AssertionError("from_pretrained drew random bits for an "
                             "initializer")

    for fn in ("normal", "uniform", "truncated_normal"):
        monkeypatch.setattr(jax.random, fn, no_bits)
    m2 = GPTForCausalLM.from_pretrained(d)
    assert {str(p.dtype) for p in m2.parameters()} == {"bfloat16"}
    for a, b in zip(m.parameters(), m2.parameters()):
        assert isinstance(b._data, jnp.ndarray)
        np.testing.assert_array_equal(
            np.asarray(a._data.astype(jnp.float32)),
            np.asarray(b._data.astype(jnp.float32)))


def test_checkpoint_that_lacks_a_parameter_is_an_error(tmp_path):
    """Parameters are abstract until the checkpoint binds them, so a
    checkpoint that lacks one cannot be served on leftover init values."""
    from paddle_infer_tpu import native

    m = _tiny_gpt()
    d = str(tmp_path / "gpt")
    m.save_pretrained(d)
    path = os.path.join(d, "model.pits")
    tensors = dict(native.load_tensors(path))
    dropped = sorted(tensors)[0]
    kept = {k: np.array(v) for k, v in tensors.items() if k != dropped}
    del tensors
    native.save_tensors(path, kept)
    with pytest.raises(ValueError, match="lacks 1 parameter"):
        GPTForCausalLM.from_pretrained(d)
