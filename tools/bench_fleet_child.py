#!/usr/bin/env python
"""Disaggregated-serving bench child: prefill/decode fleet vs one plane.

Run by hand, in a process of its own (fresh backend + fresh
process-global compile log — it builds three engines).  It needs the
device for itself, so nothing that already holds the chip may start it:
``bench.py`` is one process and starts no children.  Prints ONE JSON
line.

The workload is the ``mixed_traffic`` interference scenario: 8 clients
stream short-prompt decodes while one 192-token prompt lands
mid-stream.  The baseline is the PR-8 single-plane chunked core (the
long prefill shares ragged mixed steps with the decode rows); the
routed side is a ``prefill,decode`` fleet behind ``FleetRouter`` — the
long prompt routes to the prefill replica, chunk-prefills there, and
hands its KV pages off to the decode replica, so the decode clients
never share a step with the long prefill at all.  Compared on the
CLIENTS' observed inter-token gap p99, plus:

  - bitwise equality of the handed-off long stream vs the single-plane
    run of the same prompt (greedy — the handoff contract);
  - post-warmup compiles across both replicas during the measured pass
    (every replica owns its own compile cache, so the fleet is warmed
    replica-by-replica first);
  - router counters (handoffs, affinity hits) from the same pass.

Numbers are platform-relative; bench_diff gates them round-over-round.

Usage (standalone):
  env PYTHONPATH=. JAX_PLATFORMS=cpu python tools/bench_fleet_child.py
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    import paddle_infer_tpu as pit
    from paddle_infer_tpu.inference import (GenerationConfig,
                                            PagedGenerationEngine)
    from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_infer_tpu.observability.compilelog import get_compile_log
    from paddle_infer_tpu.serving import (EngineCore, FleetRouter,
                                          ReplicaHandle, ReplicaRole)

    pit.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    n_dec, max_new, short_len, long_len = 8, 40, 16, 192
    prefill_chunk = 24
    rng = np.random.RandomState(0)
    shorts = [rng.randint(0, cfg.vocab_size, (short_len,)).astype(np.int32)
              for _ in range(n_dec)]
    long_prompt = rng.randint(0, cfg.vocab_size,
                              (long_len,)).astype(np.int32)
    g = GenerationConfig(max_new_tokens=max_new)
    g_long = GenerationConfig(max_new_tokens=8)

    def make_core():
        return EngineCore(
            PagedGenerationEngine(model, page_size=16),
            max_batch=n_dec + 1, max_model_len=long_len + max_new,
            token_budget=32,
            prefill_chunk=prefill_chunk).start()

    def measure(submit_short, submit_long):
        """One interference pass: returns (p50, p99, long_tokens)."""
        gaps = []
        lock = threading.Lock()
        started = [0] * n_dec

        def client(i):
            r = submit_short(shorts[i])
            prev = time.perf_counter()
            for k in range(1, max_new + 1):
                try:
                    r.wait_tokens(k, timeout=300)
                except TimeoutError:
                    return
                now = time.perf_counter()
                with lock:
                    gaps.append(now - prev)
                prev = now
                started[i] = k
                if r.done and r.emitted <= k:
                    return

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_dec)]
        for t in threads:
            t.start()
        deadline = time.perf_counter() + 300
        while (min(started) < max_new // 4
               and time.perf_counter() < deadline):
            time.sleep(0.002)
        long_req = submit_long(long_prompt)
        for t in threads:
            t.join()
        long_toks = np.asarray(long_req.result(timeout=600)).tolist()
        gaps.sort()
        return (gaps[int(0.50 * (len(gaps) - 1))],
                gaps[int(0.99 * (len(gaps) - 1))], long_toks)

    # ---- baseline: single-plane chunked core (PR-8 mixed_traffic side)
    core = make_core()
    try:
        core.submit(shorts[0], g)[0].result(timeout=600)          # warm
        core.submit(long_prompt, g_long)[0].result(timeout=600)
        p50_s, p99_s, base_long = measure(
            lambda p: core.submit(p, g)[0],
            lambda p: core.submit(p, g_long)[0])
    finally:
        core.close()

    # ---- routed: prefill,decode fleet (each replica = own engine, own
    # KV pools, own compile cache; shared model)
    handles = [ReplicaHandle("prefill0", make_core(), ReplicaRole.PREFILL),
               ReplicaHandle("decode0", make_core(), ReplicaRole.DECODE)]
    router = FleetRouter(handles, prefix_affinity=True)
    router.start(start_cores=False)       # cores already started
    try:
        # warm EVERY replica: the short warms decode0's prefill/decode
        # executables, the long warms prefill0's chunk path AND the full
        # handoff (export gather + decode0's page-scatter import)
        router.submit(shorts[0], g).result(timeout=600)
        router.submit(long_prompt, g_long).result(timeout=600)
        snap0 = router.snapshot()
        compiles0 = get_compile_log().summary()[
            "post_warmup_decode_compiles"]
        p50_r, p99_r, fleet_long = measure(
            lambda p: router.submit(p, g),
            lambda p: router.submit(p, g_long))
        compiles = get_compile_log().summary()[
            "post_warmup_decode_compiles"] - compiles0
        snap = router.snapshot()
    finally:
        router.close()

    handoffs = snap["handoffs"] - snap0["handoffs"]
    print(json.dumps({
        "decode_clients": n_dec,
        "long_prompt_tokens": long_len,
        "prefill_chunk": prefill_chunk,
        "fleet_roles": "prefill,decode",
        "itl_p50_single_s": round(p50_s, 5),
        "itl_p99_single_s": round(p99_s, 5),
        "itl_p50_routed_s": round(p50_r, 5),
        "itl_p99_routed_s": round(p99_r, 5),
        "itl_p99_improvement_routed": round(p99_s / p99_r, 2),
        "handoffs": handoffs,
        "long_handed_off": bool(handoffs >= 1),
        "handoff_stream_bitwise_equal": bool(base_long == fleet_long),
        "affinity_hits": snap["affinity_hits"],
        "requeued": snap["requeued"],
        "post_warmup_compiles_routed": compiles,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
