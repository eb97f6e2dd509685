"""Bring-up smoke: the two main paths, end to end, on the attached TPU.

    python chip_smoke.py                 # one chip: serve, train, kernels
    python chip_smoke.py --chips 4       # four chips: --mp 4 serving only
    python chip_smoke.py --rehearse-cpu  # tiny sizes on the CPU backend

It is the quickest proof that the system still starts on the chip, not a
benchmark: the times it prints are smoke numbers, never metrics.

The parent process never imports JAX.  Every phase is a child that owns
the chip alone and has exited before the next one starts:

  serve    a seeded bf16 checkpoint at the ``llama-7b`` preset's widths
           (depth cut, see DEPTH) written with ``save_pretrained``, then
           ``tools/serve.py`` over HTTP — warm-up pass, measured pass,
           stream == generate, zero compilations after warm-up — then,
           after the server has exited, a child that checks the served
           greedy tokens against the eager model
  train    ERNIE-3.0-base at its real shape through ``fleet.init`` +
           ``FleetTrainStep``: a few steps, loss finite and falling, the
           compiled step holds the Pallas attention (``tpu_custom_call``)
  kernels  every Pallas entry those paths can reach, compiled by Mosaic at
           the same widths and compared with the XLA references

A phase that fails makes the script exit non-zero; nothing is skipped.
With no TPU the first child says so and the script exits non-zero without
printing a result.  The last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# Depth of the served checkpoint.  llama-7b's 32 layers are 13.5 GB in
# bf16 and leave no room for a page pool on 16 GB; 8 layers are 3.2 GB of
# blocks + 0.5 GB of embedding and head, and keep checkpoint writing,
# loading and the step's compile inside a few minutes of a 1200 s budget.
# Widths (hidden 4096, 32 heads of 128, FFN 11008, vocab 32000) are the
# preset's own.
DEPTH = 8

REAL = dict(
    llama=dict(preset="llama-7b", num_hidden_layers=DEPTH),
    serve_args=["--max_model_len", "2048", "--max_batch", "4"],
    long_prompt=1100, shared_prefix=200, short_prompt=12, mid_prompt=77,
    max_new=8, vocab=32000,
    ernie=dict(preset="ernie-3.0-base", batch=32, seq=512, vocab=40000),
    train_steps=12,
    paged=dict(b=8, h=32, d=128, page=16, max_pages=32, pool=320,
               window=4, chunk=64),
    flash=[dict(b=8, s=512, h=12, d=64, causal=False),
           dict(b=1, s=2048, h=32, d=128, causal=True)],
    # axk1-ep16.ragchat's widths (benchmarks/configs/a.x-k1-ep16-d7.json):
    # 64 heads against one 512 + 64 row a token; 12 experts of 7168 x 2048
    # over the 64 x 8 assignments a step can make
    latent=dict(b=16, h=64, rank=512, rope=64, page=16, max_pages=256,
                pool=4097, chunk=64),
    grouped=dict(rows=512, hidden=7168, ffn=2048, experts=12),
)
TINY = dict(
    llama=dict(preset=None, vocab_size=128, hidden_size=64,
               num_hidden_layers=2, num_attention_heads=4,
               intermediate_size=128, max_position_embeddings=256),
    serve_args=["--max_model_len", "192", "--max_batch", "2"],
    long_prompt=150, shared_prefix=40, short_prompt=12, mid_prompt=21,
    max_new=4, vocab=128,
    ernie=dict(preset=None, batch=4, seq=128, vocab=1024),
    train_steps=4,
    paged=dict(b=2, h=4, d=16, page=16, max_pages=4, pool=12,
               window=3, chunk=16),
    flash=[dict(b=1, s=128, h=2, d=64, causal=False),
           dict(b=1, s=256, h=2, d=64, causal=True)],
    latent=dict(b=4, h=4, rank=16, rope=8, page=16, max_pages=20, pool=24,
                chunk=16),
    grouped=dict(rows=32, hidden=64, ffn=32, experts=4),
)

CHILD_TIMEOUT_S = {"checkpoint": 420, "eager": 420, "train": 600,
                   "kernels": 600}
SERVER_START_S = 300
REQUEST_TIMEOUT_S = 600


# ===================================================================
# parent side: no JAX anywhere below this line until the children
# ===================================================================

_LIVE = []          # every process the script started and not yet reaped


def _spawn(cmd, env, **kw):
    proc = subprocess.Popen(cmd, env=env, cwd=ROOT, start_new_session=True,
                            **kw)
    _LIVE.append(proc)
    return proc


def _reap(proc, grace_s=20.0):
    """Stop ``proc`` and everything it started; returns its exit code."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
            proc.wait(timeout=grace_s)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            pass
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if proc in _LIVE:
        _LIVE.remove(proc)
    return proc.returncode


def _child_env(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()
    return env


class PhaseFailed(RuntimeError):
    pass


def run_child(args, name, *extra):
    """Run ``chip_smoke.py --child name`` to its end; echo its output;
    return the object on its ``RESULT`` line."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name,
           "--seed", str(args.seed), "--chips", str(args.chips), *extra]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    t0 = time.monotonic()
    proc = _spawn(cmd, _child_env(args), stdout=subprocess.PIPE, text=True)
    result = None
    try:
        # the child's own watchdog ends it at CHILD_TIMEOUT_S; this loop
        # ends when its stdout closes
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith("RESULT "):
                result = json.loads(line[len("RESULT "):])
            else:
                print(f"[{name}] {line}", flush=True)
        proc.wait()
    finally:
        rc = _reap(proc)
    if rc != 0 or result is None:
        raise PhaseFailed(f"{name} child failed (rc={rc})")
    result["wall_s"] = round(time.monotonic() - t0, 1)
    return result


# ------------------------------------------------------------- HTTP client

def _get(url, path, timeout=60):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return json.load(r)


def _post_lines(url, path, body):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
        return [json.loads(ln) for ln in r.read().decode().splitlines()
                if ln.strip()]


def generate(url, ids, max_new):
    (out,) = _post_lines(url, "/generate",
                         {"ids": [ids], "max_new_tokens": max_new})
    return out["tokens"][0]


def generate_stream(url, ids, max_new):
    lines = _post_lines(url, "/generate_stream",
                        {"ids": [ids], "max_new_tokens": max_new,
                         "chunk_size": 3})
    assert lines[0].get("request_ids") and "tokens" not in lines[0], lines[0]
    toks = []
    for ln in lines[1:]:
        toks.extend(ln["tokens"][0])
    return toks, len(lines) - 1


class Server:
    """tools/serve.py as a child: started with its defaults (ragged mixed
    step, page 16) plus what the caller adds; owns the chip(s) while it
    lives."""

    def __init__(self, args, model_dir, extra, log_path):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        self.url = f"http://127.0.0.1:{port}"
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = _spawn(
            [sys.executable, os.path.join(ROOT, "tools", "serve.py"),
             "--model_dir", model_dir, "--port", str(port), *extra],
            _child_env(args), stdout=self._log, stderr=subprocess.STDOUT)
        t0 = time.monotonic()
        while True:
            if self.proc.poll() is not None:
                raise PhaseFailed(
                    f"server exited rc={self.proc.returncode} before "
                    f"/health:\n{self.tail()}")
            try:
                self.health = _get(self.url, "/health", timeout=5)
                break
            except OSError:
                if time.monotonic() - t0 > SERVER_START_S:
                    raise PhaseFailed(
                        f"no /health after {SERVER_START_S}s:\n"
                        f"{self.tail()}")
                time.sleep(1.0)
        self.start_s = round(time.monotonic() - t0, 1)

    def tail(self, n=4000):
        self._log.flush()
        with open(self.log_path) as f:
            return f.read()[-n:]

    def close(self):
        rc = _reap(self.proc)
        self._log.close()
        return rc


@contextlib.contextmanager
def serving(args, model_dir, extra, log_path):
    """A Server for the body's lifetime; its log tail when the body fails."""
    srv = Server(args, model_dir, extra, log_path)
    try:
        yield srv
    except BaseException:
        print(f"server log tail:\n{srv.tail()}", flush=True)
        raise
    finally:
        srv.close()


def _prompts(spec, rng):
    """One pass of traffic: a short prompt, a mid one, one long enough
    for chunked prefill over many pages, and two that share a prefix."""
    tok = lambda n: [rng.randrange(3, spec["vocab"]) for _ in range(n)]
    shared = tok(spec["shared_prefix"])
    return {"short": tok(spec["short_prompt"]),
            "mid": tok(spec["mid_prompt"]),
            "long": tok(spec["long_prompt"]),
            "shared_a": shared + tok(9),
            "shared_b": shared + tok(14)}


def drive_server(url, spec, seed, check_prefix=True):
    """Warm-up pass, then the measured pass on fresh tokens of the same
    shapes.  Returns what the pass produced and what /metrics said."""
    out = {}
    t0 = time.monotonic()
    warm = _prompts(spec, random.Random(seed))
    for name, ids in warm.items():
        generate(url, ids, spec["max_new"])
    generate_stream(url, warm["short"], spec["max_new"])
    snap0 = _get(url, "/metrics")
    out["warmup_s"] = round(time.monotonic() - t0, 1)
    out["compile_s"] = round(snap0["compile"]["compile_wall_s_total"], 1)
    out["compilations_warmup"] = snap0["compile"]["compile_count"]

    t0 = time.monotonic()
    main = _prompts(spec, random.Random(seed + 1))
    tokens = {}
    for name, ids in main.items():
        tokens[name] = generate(url, ids, spec["max_new"])
        assert len(tokens[name]) == spec["max_new"], (name, tokens[name])
        assert all(0 <= t < spec["vocab"] for t in tokens[name]), name
    for name in ("short", "long"):
        streamed, n_chunks = generate_stream(url, main[name],
                                             spec["max_new"])
        assert streamed == tokens[name], (
            f"stream != generate for {name}: {streamed} vs {tokens[name]}")
        assert n_chunks >= 2, n_chunks
    out["measured_s"] = round(time.monotonic() - t0, 1)
    snap = _get(url, "/metrics")
    out["compilations_after_warmup"] = (
        snap["compile"]["compile_count"] - out["compilations_warmup"])
    out["post_warmup_decode_compiles"] = \
        snap["compile"]["post_warmup_decode_compiles"]
    out["tokens_generated"] = snap["counters"]["tokens_generated"]
    out["kv_pool_pages"] = snap["kv_pool"]["total_blocks"]
    out["prefix_cache"] = {k: snap["prefix_cache"][k] for k in
                           ("queries", "hits", "cached_tokens")} \
        if snap.get("prefix_cache") else None
    out["sharding"] = snap.get("sharding")
    out["prompts"], out["tokens"] = main, tokens
    assert out["compilations_after_warmup"] == 0, (
        f"{out['compilations_after_warmup']} compilation(s) after "
        f"warm-up: {snap['compile']}")
    assert out["post_warmup_decode_compiles"] == 0
    if check_prefix:
        assert out["prefix_cache"] and out["prefix_cache"]["hits"] >= 2, \
            out["prefix_cache"]
    return out


def _check_runtime(health, args):
    rt = health["runtime"]
    want = "cpu" if args.rehearse_cpu else "tpu"
    assert rt["device"]["platform"] == want, rt["device"]
    assert rt["device"]["count"] >= args.chips, rt["device"]
    if not args.rehearse_cpu:
        assert rt["param_dtypes"] == ["bfloat16"], rt["param_dtypes"]
    assert rt["native"] in ("built", "found"), rt["native"]
    return rt


def phase_serve(args, spec, workdir):
    ckpt = os.path.join(workdir, "ckpt")
    made = run_child(args, "checkpoint", "--dir", ckpt)
    print(f"[serve] checkpoint: {made}", flush=True)
    with serving(args, ckpt,
                 ["--enable_prefix_cache", *spec["serve_args"]],
                 os.path.join(workdir, "serve.log")) as srv:
        rt = _check_runtime(srv.health, args)
        print(f"[serve] server up in {srv.start_s}s on {rt['device']}; "
              f"params {rt['param_dtypes']}; native library "
              f"{rt['native']}; compile cache {rt['compile_cache_dir']}",
              flush=True)
        res = drive_server(srv.url, spec, args.seed)
        rt = _get(srv.url, "/health")["runtime"]
    peak = max((m["peak_bytes_in_use"] or 0) for m in rt["memory"])
    print(f"[serve] device {rt['device']}  param dtype "
          f"{rt['param_dtypes']}  depth {spec['llama'].get('num_hidden_layers')}"
          f"  compile {res['compile_s']}s over {res['compilations_warmup']} "
          f"programs  compilations after warm-up "
          f"{res['compilations_after_warmup']}  tokens generated "
          f"{res['tokens_generated']}  pool {res['kv_pool_pages']} pages  "
          f"prefix cache {res['prefix_cache']}  peak bytes in use {peak}  "
          f"warm-up {res['warmup_s']}s  measured pass {res['measured_s']}s",
          flush=True)
    # the server has exited and released the chip: now the eager model
    case = os.path.join(workdir, "eager_case.json")
    with open(case, "w") as f:
        json.dump({"prompt": res["prompts"]["short"],
                   "served": res["tokens"]["short"]}, f)
    eager = run_child(args, "eager", "--dir", ckpt, "--case", case)
    print(f"[serve] greedy vs eager: {eager}", flush=True)
    return {"device": rt["device"], "compile_s": res["compile_s"],
            "peak_bytes_in_use": peak, "eager": eager,
            "checkpoint_s": made["wall_s"], "server_start_s": srv.start_s,
            "warmup_s": res["warmup_s"], "measured_s": res["measured_s"]}


def phase_mp4(args, spec, workdir):
    """The path across chips and what it is compared with, nothing else:
    the same checkpoint served with --mp N, then on one chip, streams
    token-identical."""
    ckpt = os.path.join(workdir, "ckpt")
    made = run_child(args, "checkpoint", "--dir", ckpt)
    print(f"[mp] checkpoint: {made}", flush=True)
    runs = {}
    for label, extra in ((f"mp{args.chips}", ["--mp", str(args.chips)]),
                         ("single", [])):
        with serving(args, ckpt, [*extra, *spec["serve_args"]],
                     os.path.join(workdir, f"serve_{label}.log")) as srv:
            _check_runtime(srv.health, args)
            res = drive_server(srv.url, spec, args.seed,
                               check_prefix=False)
            res["runtime"] = _get(srv.url, "/health")["runtime"]
        runs[label] = res
        mem = {m["id"]: m["bytes_in_use"] for m in res["runtime"]["memory"]}
        print(f"[mp] {label}: device {res['runtime']['device']}  compile "
              f"{res['compile_s']}s  compilations after warm-up "
              f"{res['compilations_after_warmup']}  bytes in use per "
              f"device {mem}  sharding {res['sharding']}", flush=True)
    mp, single = runs[f"mp{args.chips}"], runs["single"]
    assert mp["tokens"] == single["tokens"], (
        f"--mp {args.chips} streams differ from one chip:\n"
        f"{mp['tokens']}\n{single['tokens']}")
    sh = mp["sharding"]
    assert sh and len(sh["param_devices"]) == args.chips, sh
    assert len(sh["kv_pool_devices"]) == args.chips, sh
    assert sh["sharded_params"] > 0, sh
    step = sh["step_collectives"].get("serve-step") or next(
        iter(sh["step_collectives"].values()), {})
    assert step.get("all-reduce", 0) > 0, sh["step_collectives"]
    used = [m["bytes_in_use"] for m in mp["runtime"]["memory"]]
    print(f"[mp] token-identical over {len(mp['tokens'])} requests; "
          f"weights on devices {sh['param_devices']}, KV pool on "
          f"{sh['kv_pool_devices']}; collectives in the step "
          f"{sh['step_collectives']}; bytes in use per device {used}",
          flush=True)
    return {"device": mp["runtime"]["device"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the --mp 4 serving path and the "
                         "single-chip stream it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU backend (interpreted "
                         "kernels): finds wrong paths and arguments, "
                         "proves nothing about the chip, and reports "
                         "platform cpu")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--case", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return CHILDREN[args.child](args)

    spec = TINY if args.rehearse_cpu else REAL
    t0 = time.monotonic()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    phases = {}
    try:
        if args.chips > 1:
            phases["mp"] = phase_mp4(args, spec, workdir)
        else:
            phases["serve"] = phase_serve(args, spec, workdir)
            phases["train"] = run_child(args, "train")
            print(f"[train] {phases['train']}", flush=True)
            phases["kernels"] = run_child(args, "kernels")
            print(f"[kernels] {phases['kernels']}", flush=True)
    finally:
        for proc in list(_LIVE):
            _reap(proc)
        shutil.rmtree(workdir, ignore_errors=True)
    devices = [p["device"] for p in phases.values()]
    assert all(d == devices[0] for d in devices), devices
    print(f"all phases passed in {time.monotonic() - t0:.0f}s", flush=True)
    print(json.dumps({"ok": True, "device": devices[0],
                      **({"rehearsal": True} if args.rehearse_cpu else {})}),
          flush=True)
    return 0


# ===================================================================
# children: each owns the chip for its lifetime
# ===================================================================

def _child_start(args, name):
    """Common child preamble: watchdog, compile cache, and the device —
    which must be a TPU unless this is the CPU rehearsal."""
    signal.alarm(CHILD_TIMEOUT_S[name])      # default action: terminate
    import jax

    from paddle_infer_tpu.utils.compile_cache import \
        configure_compile_cache

    cache = configure_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse_cpu and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found — JAX reports {device}. "
              "(--rehearse-cpu runs the tiny CPU rehearsal.)",
              file=sys.stderr, flush=True)
        sys.exit(3)
    print(f"device {device}  compile cache {cache}", flush=True)
    return device, (TINY if args.rehearse_cpu else REAL)


def _result(obj):
    print("RESULT " + json.dumps(obj), flush=True)
    return 0


def _llama_config(spec):
    from paddle_infer_tpu.models.llama import LlamaConfig

    kw = dict(spec["llama"])
    preset = kw.pop("preset")
    return (LlamaConfig.from_preset(preset, **kw) if preset
            else LlamaConfig(**kw))


def child_checkpoint(args):
    device, spec = _child_start(args, "checkpoint")
    import paddle_infer_tpu as pit
    from paddle_infer_tpu import native
    from paddle_infer_tpu.models.llama import LlamaForCausalLM

    pit.seed(args.seed)
    cfg = _llama_config(spec)
    t0 = time.monotonic()
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    model.save_pretrained(args.dir)
    n_params = sum(int(p.size) for p in model.parameters())
    size = sum(os.path.getsize(os.path.join(args.dir, f))
               for f in os.listdir(args.dir))
    return _result({
        "device": device, "layers": cfg.num_hidden_layers,
        "hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
        "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
        "params": n_params, "bytes_on_disk": size,
        "native_library": native.build_status(),
        "write_s": round(time.monotonic() - t0, 1)})


def child_eager(args):
    """The served greedy tokens against the eager model (verify flow 13's
    per-token loop, teacher-forced: under causal attention one eager pass
    over prompt + served tokens yields every step's logits).  Eager ops
    and the served program round bf16 at different points, so a served
    token must be the eager argmax or tie with it within bf16 resolution
    of the logits; the count of exact argmax matches is printed."""
    device, spec = _child_start(args, "eager")
    import numpy as np

    from paddle_infer_tpu.core.tensor import Tensor
    from paddle_infer_tpu.models import AutoModel

    with open(args.case) as f:
        case = json.load(f)
    prompt, served = case["prompt"], case["served"]
    t0 = time.monotonic()
    model = AutoModel.from_pretrained(args.dir)
    dtypes = sorted({str(p.dtype) for p in model.parameters()})
    ids = np.asarray(prompt + served[:-1], np.int32)[None, :]
    logits = np.asarray(model(Tensor(ids))._data.astype("float32"))[0]
    assert np.isfinite(logits).all()
    steps = logits[len(prompt) - 1:]                   # one row per token
    assert steps.shape[0] == len(served), (steps.shape, len(served))
    top = steps.max(axis=-1)
    got = steps[np.arange(len(served)), served]
    # bf16 keeps 8 significant bits: two roundings of a logit of this
    # magnitude can differ by a few units of 2**-8 relative
    tol = 4 * 2.0 ** -8 * np.maximum(np.abs(top), 1.0) \
        if "bfloat16" in dtypes else 1e-4
    exact = int((steps.argmax(axis=-1) == np.asarray(served)).sum())
    worst = float((top - got).max())
    assert ((top - got) <= tol).all(), (
        f"served tokens are not the eager greedy tokens: exact "
        f"{exact}/{len(served)}, worst logit gap {worst} > tol {tol}")
    return _result({"device": device, "param_dtypes": dtypes,
                    "exact_argmax": f"{exact}/{len(served)}",
                    "worst_logit_gap": round(worst, 5),
                    "eager_s": round(time.monotonic() - t0, 1)})


def child_train(args):
    device, spec = _child_start(args, "train")
    import jax
    import numpy as np

    import paddle_infer_tpu as pit
    from paddle_infer_tpu.models import (ErnieConfig, ErnieForPretraining,
                                         ernie_pretrain_loss)
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, fleet)

    e = spec["ernie"]
    batch, seq = e["batch"], e["seq"]
    common = dict(vocab_size=e["vocab"], max_position_embeddings=seq,
                  hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1)
    cfg = (ErnieConfig.from_preset(e["preset"], **common) if e["preset"]
           else ErnieConfig(hidden_size=128, num_hidden_layers=2,
                            num_attention_heads=4, intermediate_size=512,
                            **common))
    # exactly as bench.py builds it: dp=1 on one device, AMP O2 bf16
    pit.seed(args.seed)
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1}
    strategy.amp = True
    strategy.amp_configs = {"level": "O2", "dtype": "bfloat16"}
    fleet.init(is_collective=True, strategy=strategy,
               devices=jax.devices()[:1])
    model = ErnieForPretraining(cfg)
    model.train()
    opt = pit.optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())

    def loss_fn(m, ids, mask, labels, nsp_labels):
        mlm, nsp = m(ids, attention_mask=mask)
        return ernie_pretrain_loss(mlm, nsp, labels, nsp_labels)

    step = FleetTrainStep(model, loss_fn, opt, strategy=strategy)
    rng = np.random.RandomState(args.seed)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    pad = max(1, seq // 10)            # 10 % trailing padding per row,
    mask = np.ones((batch, seq), np.int32)   # carried as segment ids
    mask[:, seq - pad:] = 0
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels[:, seq - pad:] = -100
    nsp = rng.randint(0, 2, (batch,)).astype(np.int32)

    t0 = time.monotonic()
    losses = [float(step(ids, mask, labels, nsp).numpy())]
    compile_s = time.monotonic() - t0
    walls = []
    for _ in range(spec["train_steps"] - 1):
        t0 = time.monotonic()
        losses.append(float(step(ids, mask, labels, nsp).numpy()))
        walls.append(time.monotonic() - t0)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    text = step.compiled_text(ids, mask, labels, nsp)
    n_kernels = text.count("tpu_custom_call")
    if not args.rehearse_cpu:
        # hybrid attention at seq 512: XLA forward, Pallas dK/dV and dQ
        # backward — two Mosaic calls per layer, none on the XLA
        # reference path
        assert n_kernels >= 2 * cfg.num_hidden_layers, (
            f"{n_kernels} tpu_custom_call in the compiled step; the "
            "attention backward is not the Pallas kernel")
    return _result({
        "device": device, "layers": cfg.num_hidden_layers,
        "batch": batch, "seq": seq, "losses": [round(l, 4) for l in losses],
        "first_step_s_with_compile": round(compile_s, 1),
        "step_wall_s_after_warmup": round(sorted(walls)[len(walls) // 2], 4),
        "tpu_custom_calls_in_step": n_kernels})


def child_kernels(args):
    """Each Pallas entry the serve and train paths (and serving's
    options) can reach, compiled by Mosaic — never interpreted — at the
    phases' widths, against ``prefix_prefill_attention`` (the XLA gather
    composition), ``_ragged_reference`` and ``_xla_sdpa``."""
    device, spec = _child_start(args, "kernels")
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_infer_tpu.ops import pallas
    from paddle_infer_tpu.ops.attention import _xla_sdpa
    from paddle_infer_tpu.ops.pallas import flash_attention as FA
    from paddle_infer_tpu.ops.pallas import paged_attention as PA
    from paddle_infer_tpu.ops.pallas import ragged_paged_attention as RPA

    if not args.rehearse_cpu:
        assert pallas.interpret() is False, "kernels would be interpreted"
    report = {}

    def close(name, got, want, tol=3e-2):
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want.astype(jnp.float32))
        assert got.shape == want.shape, (name, got.shape, want.shape)
        assert np.isfinite(got).all(), f"{name}: non-finite output"
        err = float(np.max(np.abs(got - want)) / max(
            float(np.max(np.abs(want))), 1e-6))
        assert err < tol, f"{name}: normalized max error {err} >= {tol}"
        report[name] = round(err, 5)
        print(f"{name}: ok, normalized max error {err:.2e}", flush=True)

    def compiled(fn, *a):
        """Compile, and on the chip prove Mosaic put a kernel into it."""
        exe = jax.jit(fn).lower(*a).compile()
        if not args.rehearse_cpu:
            assert "tpu_custom_call" in exe.as_text(), fn
        return exe(*a)

    # ---- paged kernels over one random pool, bf16 and int8 pages
    p = spec["paged"]
    b, h, d, page, mp = p["b"], p["h"], p["d"], p["page"], p["max_pages"]
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    kp = jax.random.normal(ks[0], (p["pool"], h, page, d), jnp.bfloat16)
    vp = jax.random.normal(ks[1], (p["pool"], h, page, d), jnp.bfloat16)
    rs = np.random.RandomState(args.seed)
    tables = jnp.asarray(np.stack([
        rs.permutation(p["pool"])[:mp] for _ in range(b)]), jnp.int32)
    w, c = p["window"], p["chunk"]
    ctx_np = rs.randint(1, mp * page - c - 1, (b,))
    ctx_np[0] = 1                          # a row with one cached token
    ctx_np[-1] = mp * page - c - 1         # a row that walks every page
    ctx = jnp.asarray(ctx_np, jnp.int32)
    q1 = jax.random.normal(ks[2], (b, h, d), jnp.bfloat16)
    qw = jax.random.normal(ks[3], (b, w, h, d), jnp.bfloat16)
    qc = jax.random.normal(ks[4], (b, c, h, d), jnp.bfloat16)
    qlens_np = rs.randint(1, c + 1, (b,))
    qlens_np[0], qlens_np[1] = 1, c        # a decode row, a full chunk
    if b > 2:
        qlens_np[2] = 0                    # an inactive row
    qlens = jnp.asarray(qlens_np, jnp.int32)
    lens_w = ctx[:, None] + jnp.arange(w, dtype=jnp.int32)[None] + 1
    valid = np.arange(c)[None] < qlens_np[:, None]

    with jax.default_matmul_precision("highest"):
        for tag, (kpool, vpool) in (
                ("bf16", (kp, vp)),
                ("int8", (PA.quantize_pages(kp), PA.quantize_pages(vp)))):
            ref = jax.jit(PA.prefix_prefill_attention)
            close(f"paged_attention_decode[{tag}]",
                  compiled(PA.paged_attention_decode, q1, kpool, vpool,
                           tables, ctx + 1),
                  ref(q1[:, None], kpool, vpool, tables, ctx)[:, 0])
            close(f"paged_attention_verify[{tag}]",
                  compiled(PA.paged_attention_verify, qw, kpool, vpool,
                           tables, lens_w),
                  ref(qw, kpool, vpool, tables, ctx))
            # the served entry point against the plain composition, on
            # decode, chunk, inactive and verify rows (bf16 results: one
            # ulp is 4e-3 of the value)
            keep = jnp.asarray(valid)[:, :, None, None]
            for name, kw in (("", {}), ("+verify", dict(
                    verify_rows=jnp.asarray(qlens_np > w),
                    verify_window=w))):
                got = compiled(
                    functools.partial(RPA.ragged_paged_attention, **kw),
                    qc, kpool, vpool, tables, ctx, qlens)
                want = jax.jit(functools.partial(
                    RPA._ragged_reference, **kw))(
                    qc, kpool, vpool, tables, ctx, qlens)
                close(f"ragged_paged_attention{name}[{tag}]",
                      jnp.where(keep, got, 0), jnp.where(keep, want, 0),
                      tol=1e-2)
            # float32 queries give a float32 result: the two sides then
            # differ by the online softmax's reassociation alone, and
            # probabilities rounded to bf16 (1e-3) would show
            qf = qc.astype(jnp.float32)
            close(f"ragged_paged_attention float32 queries[{tag}]",
                  jnp.where(keep, compiled(
                      RPA.ragged_paged_attention, qf, kpool, vpool,
                      tables, ctx, qlens), 0),
                  jnp.where(keep, jax.jit(RPA._ragged_reference)(
                      qf, kpool, vpool, tables, ctx, qlens), 0),
                  tol=1e-4)
            # its decode rows take the decode kernel's own page step, and
            # a position's bits do not depend on how its chunk was cut
            served = jax.jit(RPA.ragged_paged_attention)
            ones = jnp.ones_like(qlens)
            assert np.array_equal(
                np.asarray(served(qc, kpool, vpool, tables, ctx,
                                  ones)[:, 0].astype(jnp.float32)),
                np.asarray(PA.paged_attention_decode(
                    qc[:, 0], kpool, vpool, tables,
                    ctx + 1).astype(jnp.float32))), tag
            half = c // 2
            whole = served(qc[:1], kpool, vpool, tables[:1], ctx[:1],
                           jnp.asarray([c], jnp.int32))[0]
            cut = served(
                jnp.stack([qc[0], jnp.roll(qc[0], -half, axis=0)]), kpool,
                vpool, jnp.tile(tables[:1], (2, 1)),
                jnp.stack([ctx[0], ctx[0] + half]),
                jnp.asarray([half, c - half], jnp.int32))
            assert np.array_equal(
                np.asarray(whole.astype(jnp.float32)),
                np.asarray(jnp.concatenate(
                    [cut[0, :half], cut[1, :c - half]]).astype(
                        jnp.float32))), tag
            report[f"ragged_schedule_independent[{tag}]"] = True
            print(f"ragged decode rows and chunk cuts bitwise [{tag}]: ok",
                  flush=True)

    # ---- latent pages: the decode kernel against the chunk composition
    from paddle_infer_tpu.ops.pallas import grouped_matmul as GM
    from paddle_infer_tpu.ops.pallas import latent_attention as LA

    p = spec["latent"]
    b, h, rank, page, mp = p["b"], p["h"], p["rank"], p["page"], p["max_pages"]
    width = rank + p["rope"]
    lk = jax.random.split(jax.random.PRNGKey(args.seed + 7), 4)
    lanes = -(-width // 128) * 128         # the pool's rows, zero-padded
    lpool = LA.pad_lanes(jax.random.normal(
        lk[0], (p["pool"], page, width), jnp.bfloat16), lanes)
    ltab = jnp.asarray(np.stack([
        rs.permutation(p["pool"])[:mp] for _ in range(b)]), jnp.int32)
    lctx_np = rs.randint(1, mp * page - 2, (b,))
    lctx_np[0], lctx_np[-1] = 1, mp * page - 2    # one token; every page
    lctx = jnp.asarray(lctx_np, jnp.int32)
    # queries scaled so the softmax is neither flat nor one-hot
    lq = jax.random.normal(lk[1], (b, h, width), jnp.bfloat16)
    scale = 1.0 / float(np.sqrt(width))
    # each query as the first of a chunk of two, the chunks end to end
    # on the composition's flat token axis
    composed = jax.jit(lambda q, pool, t, c: LA.latent_chunk_attention(
        jnp.stack([q, jnp.zeros_like(q)], 1).reshape(
            (2 * b,) + q.shape[1:]).astype(jnp.float32),
        pool.astype(jnp.float32), t, c, jnp.full((b,), 2, jnp.int32),
        scale, rank)[0::2])
    # the served step's mix beside the whole table: every third row dead
    # among live ones, the longest live row a walk of three grid steps of
    # the table's many, lengths on a grid step's border and one past it.
    # ONE executable: the grid's bounds are traced values
    _, span, table_steps = LA.walk_geometry(page, mp)
    mixed_np = rs.randint(1, 2 * span + 2, (b,))
    mixed_np[0], mixed_np[-1] = span, 2 * span + 1
    mixed_np[1::3] = 0
    decode = jax.jit(lambda q, pool, t, n: LA.latent_paged_decode(
        q, pool, t, n, scale, rank)).lower(
            lq, lpool, ltab, lctx + 1).compile()
    if not args.rehearse_cpu:
        assert "tpu_custom_call" in decode.as_text()
    for name, n_np in (("latent_paged_decode", lctx_np + 1),
                       ("latent_paged_decode[live rows]", mixed_np)):
        n = jnp.asarray(n_np, jnp.int32)
        got = decode(lq, lpool, ltab, n)
        alive = (n > 0)[:, None, None]
        assert not np.asarray(jnp.where(alive, 0, got).astype(
            jnp.float32)).any(), f"{name}: a dead row does not read zero"
        with jax.default_matmul_precision("highest"):
            want = composed(lq, lpool, ltab, jnp.maximum(n - 1, 0))
        close(name, got, jnp.where(alive, want, 0))
        rows, walk = (int(x) for x in LA.decode_grid(n, page, mp)[1:])
        print(f"{name}: grid {rows} x {walk} of {b} x {table_steps}",
              flush=True)

    # ---- grouped matmul: sorted rows, uneven groups, an untouched expert
    g = spec["grouped"]
    gk = jax.random.split(jax.random.PRNGKey(args.seed + 8), 2)
    rows = jax.random.normal(gk[0], (g["rows"], g["hidden"]), jnp.bfloat16)
    wmat = jax.random.normal(gk[1], (g["experts"], g["hidden"], g["ffn"]),
                             jnp.bfloat16) * 0.02
    sizes_np = np.zeros((g["experts"],), np.int32)
    sizes_np[0], sizes_np[-1] = 3, g["rows"] // 4 + 1
    sizes_np[1] = g["rows"] // 2 - 2             # straddles row tiles
    sizes = jnp.asarray(sizes_np)
    got = compiled(GM.grouped_matmul, rows, wmat, sizes)
    with jax.default_matmul_precision("highest"):
        parts, r0 = [], 0
        for e, n in enumerate(sizes_np):          # expert by expert
            parts.append(rows[r0:r0 + n].astype(jnp.float32)
                         @ wmat[e].astype(jnp.float32))
            r0 += int(n)
    want = jnp.concatenate(parts + [jnp.zeros(
        (g["rows"] - r0, g["ffn"]), jnp.float32)])
    close("moe_grouped_matmul", got, want)

    # ---- flash / hybrid, forward and backward, segment ids + dropout
    for shape in spec["flash"]:
        fb, s, fh, fd, causal = (shape[k] for k in
                                 ("b", "s", "h", "d", "causal"))
        fk = jax.random.split(jax.random.PRNGKey(args.seed + s), 3)
        q, k, v = (jax.random.normal(x, (fb, s, fh, fd), jnp.bfloat16)
                   for x in fk)
        # trailing tenth is padding: its own segment id
        seg = jnp.broadcast_to(
            (jnp.arange(s) < s - s // 10).astype(jnp.int32)[None], (fb, s))
        seed = jnp.uint32(1234 + args.seed)

        def loss_of(attn):
            def f(q_, k_, v_):
                o = attn(q_, k_, v_)
                return jnp.sum(o.astype(jnp.float32) ** 2), o
            return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

        with jax.default_matmul_precision("highest"):
            (_, ro), rg = jax.jit(loss_of(
                lambda q_, k_, v_: _xla_sdpa(
                    q_, k_, v_, None, seed, 0.1, causal, None,
                    q_segment_ids=seg, kv_segment_ids=seg)))(q, k, v)
        for name, fn in (("flash_attention", FA.flash_attention),
                         ("hybrid_attention", FA.hybrid_attention)):
            (_, o), g = compiled(loss_of(
                lambda q_, k_, v_: fn(
                    q_, k_, v_, q_segment_ids=seg, kv_segment_ids=seg,
                    dropout_p=0.1, dropout_seed=seed, is_causal=causal)),
                q, k, v)
            tag = f"{name}[b{fb} s{s} h{fh} d{fd}]"
            close(f"{tag} fwd", o, ro)
            for gname, a, r in zip(("dq", "dk", "dv"), g, rg):
                close(f"{tag} {gname}", a, r, tol=5e-2)
    return _result({"device": device, "normalized_max_error": report})


CHILDREN = {"checkpoint": child_checkpoint, "eager": child_eager,
            "train": child_train, "kernels": child_kernels}


if __name__ == "__main__":
    sys.exit(main())
