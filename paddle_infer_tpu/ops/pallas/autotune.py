"""Kernel autotuner: measured block-size selection with a persistent
cache.

Reference: paddle/phi/kernels/autotune/ — AutoTuneBase::Run times kernel
candidates per shape key (auto_tune_base.h), AutoTuneCache keeps the
winner per (algo, key) and serializes across runs (cache.h), gated by a
switch (``EnableAutoTune``).

TPU redesign: the tunables are Pallas grid block sizes, not cuDNN algo
enums.  Tuning happens at *trace time* with concrete dummy operands (the
live values are tracers), so one benchmark per (kernel, shape) services
every retrace; winners persist to ``FLAGS_autotune_cache_file`` — by
default a fixed file in the checkout, so a serving restart (or the next
process of the same run) pays nothing.  The incumbent default must lose
by >3% to be replaced — noisy timings never regress the shipped
configuration.
"""
from __future__ import annotations

import json
import logging
import os
import time
from typing import Callable, Dict, Sequence

from ...framework.flags import define_flag, flags
from ...utils.compile_cache import CHECKOUT
from . import interpret

define_flag("use_autotune", True,
            "measure Pallas kernel block-size candidates per shape and "
            "cache the winner (reference phi/kernels/autotune)")
define_flag("autotune_cache_file", "",
            "JSON file persisting autotune winners across processes "
            "(empty: .autotune_cache.json at the checkout root)")

_LOG = logging.getLogger(__name__)
_DEFAULT_CACHE_FILE = os.path.join(CHECKOUT, ".autotune_cache.json")
_CACHE: Dict[str, list] = {}
_LOADED = False
_MIN_GAIN = 0.97     # challenger must beat the incumbent by >3%


def _cache_path() -> str:
    # a fixed path, never a temp name: the processes of one run (and the
    # next run in the same checkout) must find each other's winners
    return flags("autotune_cache_file") or _DEFAULT_CACHE_FILE


def _load():
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    p = _cache_path()
    if os.path.exists(p):
        with open(p) as f:
            _CACHE.update(json.load(f))


def _persist():
    p = _cache_path()
    tmp = f"{p}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(_CACHE, f)
    os.replace(tmp, p)


def enabled() -> bool:
    """Compiled kernels only: timing the interpreter tunes nothing."""
    return not interpret() and bool(flags("use_autotune"))


def clear():
    _CACHE.clear()


def autotune(key: str, default, candidates: Sequence,
             measure: Callable[[object], float]):
    """Return the cached winner for ``key`` or measure ``candidates``
    (incumbent ``default`` first; challengers must beat it by >3%).
    ``measure(cand) -> seconds`` should include compile via a warmup call
    so only steady-state time is compared."""
    if not enabled():
        return default
    import jax

    _load()
    # winners are per chip generation: a file carried to another device
    # must miss, not answer
    key = f"{jax.devices()[0].device_kind}|{key}"
    hit = _CACHE.get(key)
    if hit is not None:
        return tuple(hit) if isinstance(hit, list) else hit
    # the incumbent is what ships: if IT cannot compile or run, that is
    # the caller's error to see, not something to tune around
    best, best_t = default, measure(default)
    for cand in candidates:
        if cand == default:
            continue
        try:
            t = measure(cand)
        except Exception as e:      # challenger invalid for this shape
            _LOG.warning("autotune %s: candidate %r skipped: %r",
                         key, cand, e)
            continue
        if t < best_t * _MIN_GAIN:
            best, best_t = cand, t
    _CACHE[key] = list(best) if isinstance(best, tuple) else best
    _persist()
    return best


def time_fn(fn: Callable[[], object], iters: int = 3) -> float:
    """Median wall time of ``fn`` after a compile/warmup call; results
    must expose block_until_ready (jax arrays / pytrees)."""
    import jax

    out = fn()
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]
