"""Fleet: the distributed-training facade + compiled hybrid-parallel step.

Reference: python/paddle/distributed/fleet/fleet.py:107 (``fleet.init``,
``distributed_model`` :1038, ``distributed_optimizer`` :175) configured by a
``DistributedStrategy`` (fleet/base/distributed_strategy.py, proto
framework/distributed_strategy.proto), executing via per-op NCCL collectives,
EagerReducer gradient bucketing (collective/reducer.h:88) and the
GroupSharded ZeRO stages (meta_parallel/sharding/group_sharded_stage{2,3}.py).

TPU-first redesign: ``fleet.init`` builds ONE named mesh (topology.py) and
``FleetTrainStep`` compiles the whole step — forward, loss, backward,
grad-clip, optimizer — into a single pjit program whose parameter/optimizer
shardings encode the parallelism:

  * DP: batch sharded over "dp"; GSPMD inserts the gradient all-reduce the
    EagerReducer does by hand (bucketing/fusion = XLA collective combining).
  * TP: params carry ``dist_attr`` specs from mp_layers; activations pinned
    by sharding_constraint ops.
  * ZeRO (reference group_sharded stages / DygraphShardingOptimizer):
      stage 1 "os"    → optimizer state sharded over "sharding",
      stage 2 "os_g"  → + gradients reduce-scattered onto "sharding",
      stage 3 "p_g_os"→ + parameters sharded (FSDP); XLA all-gathers weights
                        per-layer in forward exactly where stage-3's
                        _sync_params hooks did.
  * Recompute (reference recompute meta-optimizer) → jax.checkpoint.
  * AMP (reference amp meta-optimizer) → autocast state traced into the step.
  * Gradient merge (reference gradient_merge meta-optimizer) → lax.scan
    accumulation over micro-batches.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import random as prandom
from ..core.tensor import Tensor
from ..core import dispatch as dispatch_mod
from ..nn.layer import Layer
from ..observability.compilelog import get_compile_log, signature_of
from . import topology
from .topology import HybridCommunicateGroup


class DistributedStrategy:
    """Strategy knobs (reference: fleet/base/distributed_strategy.py; the
    proto-backed config surface).  Only fields the TPU build consumes are
    kept; unknown reference fields are accepted and ignored via kwargs."""

    def __init__(self, **kw):
        self.hybrid_configs: Dict[str, int] = {
            "dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
            "sharding_degree": 1, "sep_degree": 1, "ep_degree": 1}
        self.sharding = False
        self.sharding_configs: Dict[str, Any] = {"stage": 1}
        self.amp = False
        self.amp_configs: Dict[str, Any] = {"level": "O1",
                                            "dtype": "bfloat16"}
        self.recompute = False
        self.recompute_configs: Dict[str, Any] = {}
        self.gradient_merge = False
        self.gradient_merge_configs: Dict[str, Any] = {"k_steps": 1}
        # DP-only meta-optimizers (reference localsgd_optimizer.py /
        # dgc_optimizer.py) — routed by meta_optimizers.
        # distributed_train_step; FleetTrainStep refuses them so the flags
        # can never silently no-op
        self.localsgd = False
        self.localsgd_configs: Dict[str, Any] = {"k_steps": 4}
        self.dgc = False
        self.dgc_configs: Dict[str, Any] = {
            "rampup_begin_step": 0, "sparsity": 0.75}
        self.pipeline_configs: Dict[str, Any] = {"accumulate_steps": 1}
        for k, v in kw.items():
            setattr(self, k, v)

    @property
    def sharding_stage(self) -> int:
        return int(self.sharding_configs.get("stage", 1)) if self.sharding \
            else 0


class _FleetState:
    def __init__(self):
        self.strategy: Optional[DistributedStrategy] = None
        self.hcg: Optional[HybridCommunicateGroup] = None
        self.initialized = False


_state = _FleetState()


def init(role_maker=None, is_collective: bool = True,
         strategy: Optional[DistributedStrategy] = None, devices=None):
    """Build the hybrid mesh from strategy.hybrid_configs
    (reference: fleet.py:175 — role-maker env parse + HCG construction)."""
    strategy = strategy or DistributedStrategy()
    hc = strategy.hybrid_configs
    n_dev = len(devices) if devices is not None else len(jax.devices())
    degrees = {k: int(hc.get(k, 1)) for k in
               ("dp_degree", "mp_degree", "pp_degree", "sharding_degree",
                "sep_degree", "ep_degree")}
    others = int(np.prod([v for k, v in degrees.items()
                          if k != "dp_degree"]))
    if degrees["dp_degree"] <= 0:   # -1 → infer dp from device count
        degrees["dp_degree"] = max(n_dev // others, 1)
    prod = degrees["dp_degree"] * others
    if prod != n_dev and degrees["dp_degree"] == 1 and prod < n_dev \
            and n_dev % prod == 0:
        degrees["dp_degree"] = n_dev // prod
    hcg = HybridCommunicateGroup(
        dp_degree=degrees["dp_degree"], mp_degree=degrees["mp_degree"],
        pp_degree=degrees["pp_degree"],
        sharding_degree=degrees["sharding_degree"],
        sep_degree=degrees["sep_degree"],
        ep_degree=degrees["ep_degree"], devices=devices)
    _state.strategy = strategy
    _state.hcg = hcg
    _state.initialized = True
    topology.set_hybrid_communicate_group(hcg)
    return hcg


def get_hybrid_communicate_group():
    # topology holds the single source of truth (set by fleet.init or by
    # topology.set_hybrid_communicate_group directly)
    return topology.get_hybrid_communicate_group()


def fleet_strategy() -> Optional[DistributedStrategy]:
    return _state.strategy


def distributed_model(model: Layer) -> Layer:
    """Mark a model for hybrid execution (reference: fleet/model.py:29 —
    which wraps in DataParallel/TensorParallel/PipelineParallel; under SPMD
    the wrap is a no-op: the mesh + specs carry the parallelism)."""
    if not _state.initialized:
        raise RuntimeError("call fleet.init(...) before distributed_model")
    model._fleet_distributed = True
    return model


def distributed_optimizer(optimizer, strategy=None):
    """(reference: fleet.py:175 distributed_optimizer → meta-optimizer
    stack; here the step builder consumes the strategy directly.)"""
    optimizer._fleet_strategy = strategy or _state.strategy
    return optimizer


# ----------------------------------------------------------- spec derivation

def _pad_spec(spec, ndim):
    spec = tuple(spec) if spec else ()
    return spec + (None,) * (ndim - len(spec))


def param_partition_spec(name: str, arr, dist_attr, strategy,
                         mesh) -> P:
    """Partition spec for one parameter: TP spec from dist_attr, plus FSDP
    ("sharding" axis) on the first free divisible dim when stage 3."""
    ndim = arr.ndim
    spec = list(_pad_spec(dist_attr, ndim))
    # rank-1 params (biases, LN scales) stay replicated: their memory is
    # negligible and forcing "sharding" onto them makes GSPMD propagate a
    # transposed tile assignment up the grad-reduce chain (involuntary full
    # rematerialization of the activation grads)
    if strategy and strategy.sharding_stage >= 3 and ndim >= 2:
        size = mesh.shape.get("sharding", 1)
        if size > 1 and spec[0] is None and arr.shape[0] % size == 0:
            spec[0] = "sharding"      # dim-0 only, like the grad pin
    return P(*spec)


def _named_sharding(mesh, pspec):
    return NamedSharding(mesh, pspec)


def _tree_shardings(mesh, specs):
    return jax.tree_util.tree_map(
        lambda s: _named_sharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def batch_arrays(batch) -> tuple:
    """Tensor/ndarray batch -> jax arrays (shared by all step flavors)."""
    return tuple(b._data if isinstance(b, Tensor) else jnp.asarray(b)
                 for b in batch)


def batch_signature(arrays, static_kwargs) -> tuple:
    """The compiled-cache key: batch shapes/dtypes + static kwargs."""
    return tuple((a.shape, str(a.dtype)) for a in arrays) + \
        tuple(sorted(static_kwargs.items()))


def lr_scheduler_tick(optimizer):
    """Advance the optimizer's LR scheduler by one step if it has one —
    shared by every compiled train-step flavor."""
    if hasattr(optimizer._lr, "step"):
        try:
            optimizer._lr.step()
        except TypeError:
            pass


def make_pure_loss(model: Layer, loss_fn: Callable, strategy,
                   static_kwargs) -> Callable:
    """``(params, buffers, key, batch_arrays) -> (f32 scalar, new_buffers)``
    closure over the eager model — the traced core every compiled train
    step (FleetTrainStep, the LocalSGD/DGC meta-optimizer steps) shares.
    Applies the strategy's AMP autocast state and recompute wrapping.

    Buffer mutations inside the forward (BN running stats via
    ``jit.trace.update_buffer``) are captured by a trace scope and
    returned functionally — same contract as ``jit.to_static`` — instead
    of ``set_value``-ing a traced array into the eager buffer (which
    would both freeze the stats and poison the buffer with a leaked
    tracer)."""
    from ..jit.trace import trace_scope

    buf_names = {id(b): n for n, b in model.named_buffers()}

    def pure(params, buffers, key, batch):
        with trace_scope() as scope, prandom.trace_key_scope(key):
            prev_amp = None
            if strategy.amp:
                from ..core.dtype import convert_dtype

                prev_amp = dispatch_mod.set_amp_state(
                    True, convert_dtype(
                        strategy.amp_configs.get("dtype", "bfloat16")),
                    strategy.amp_configs.get("level", "O1"))
            try:
                tensors = [Tensor(b) for b in batch]
                loss = loss_fn(
                    model.functional_caller(params, buffers), *tensors,
                    **static_kwargs)
            finally:
                if prev_amp is not None:
                    dispatch_mod.set_amp_state(
                        prev_amp["enabled"], prev_amp["dtype"],
                        prev_amp["level"])
        new_buffers = dict(buffers)
        for t, arr in scope.buffer_updates:
            name = buf_names.get(id(t))
            if name is not None and name in new_buffers:
                new_buffers[name] = arr.astype(new_buffers[name].dtype)
        arr = loss._data if isinstance(loss, Tensor) else loss
        return arr.astype(jnp.float32), new_buffers

    if strategy.recompute:
        pure = jax.checkpoint(pure, static_argnums=())
    return pure


class FleetTrainStep:
    """One compiled SPMD program for the whole training step.

    ``loss_fn(model, *batch) -> scalar-loss Tensor`` is user code written in
    eager ops; it is traced through the layer's functional bridge.  The
    compiled program is cached per batch signature (the executable cache
    that replaces InterpreterCore, reference interpretercore.h:39).
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 strategy: Optional[DistributedStrategy] = None,
                 hcg: Optional[HybridCommunicateGroup] = None,
                 batch_spec: Optional[tuple] = None,
                 donate: bool = True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.strategy = strategy or _state.strategy or DistributedStrategy()
        if getattr(self.strategy, "localsgd", False) \
                or getattr(self.strategy, "dgc", False):
            raise ValueError(
                "strategy.localsgd/dgc need their own step schedule — "
                "use parallel.distributed_train_step(...) (routes to "
                "LocalSGDTrainStep / DGCTrainStep)")
        self.hcg = hcg or _state.hcg
        if self.hcg is None:
            raise RuntimeError("fleet.init(...) must run before FleetTrainStep")
        self.mesh = self.hcg.mesh
        self.batch_spec = batch_spec  # PartitionSpec per batch leaf; default dp
        self.donate = donate
        self._step_count = 0
        self._cache = {}

        # device state (sharded pytrees)
        self._param_info = [(n, p) for n, p in model.named_parameters()
                            if not p.stop_gradient]
        self._param_specs = {
            n: param_partition_spec(n, p._data, p.dist_attr, self.strategy,
                                    self.mesh)
            for n, p in self._param_info}
        self.params = self._place_params()
        # non-trainable state (BN running stats etc.) carried through the
        # compiled step functionally, replicated over the mesh
        self._buffer_info = list(model.named_buffers())
        rep_sh = _named_sharding(self.mesh, P())
        self.buffers = {n: jax.device_put(b._data, rep_sh)
                        for n, b in self._buffer_info}
        self.opt_state = None
        self._opt_specs = None

    # -------------------------------------------------------------- placing
    def _place_params(self):
        out = {}
        for n, p in self._param_info:
            sh = _named_sharding(self.mesh, self._param_specs[n])
            out[n] = jax.device_put(p._data, sh)
        return out

    def _init_opt_state(self):
        state = self.optimizer.functional_init(self.params)
        # ZeRO-1/2: optimizer slots sharded over "sharding" even when the
        # param is not (reference DygraphShardingOptimizer:28); slots always
        # inherit the param's TP spec.
        stage = self.strategy.sharding_stage
        shard_size = self.mesh.shape.get("sharding", 1)

        def slot_spec(pname, slot_arr):
            pspec = self._param_specs[pname]
            if slot_arr.ndim == 0:
                return P()
            if slot_arr.shape == self.params[pname].shape:
                spec = list(_pad_spec(tuple(pspec), slot_arr.ndim))
                # rank>=2, dim-0 only — see param_partition_spec
                if stage >= 1 and stage < 3 and shard_size > 1 \
                        and slot_arr.ndim >= 2 and spec[0] is None \
                        and slot_arr.shape[0] % shard_size == 0:
                    spec[0] = "sharding"
                return P(*spec)
            return P()

        self._opt_specs = {
            n: {k: slot_spec(n, a) for k, a in slots.items()}
            for n, slots in state.items()}
        self.opt_state = {
            n: {k: jax.device_put(a, self._opt_sharding(
                self._opt_specs[n][k]))
                for k, a in slots.items()}
            for n, slots in state.items()}

    def _offload_active(self) -> bool:
        """Optimizer-state host offload (reference GroupSharded offload
        variants): TPU only — XLA streams the slots HBM↔host around the
        update; on CPU meshes the flag quietly no-ops."""
        return bool(self.strategy.sharding
                    and self.strategy.sharding_configs.get("offload")
                    and jax.devices()[0].platform == "tpu")

    def _opt_sharding(self, pspec):
        sh = _named_sharding(self.mesh, pspec)
        if self._offload_active():
            try:
                sh = sh.with_memory_kind("pinned_host")
            except Exception:
                pass
        return sh

    # ------------------------------------------------------------- building
    def _pure_loss(self, static_kwargs):
        return make_pure_loss(self.model, self.loss_fn, self.strategy,
                              static_kwargs)

    def _build(self, batch_sig, static_kwargs):
        strategy = self.strategy
        mesh = self.mesh
        pure_loss = self._pure_loss(static_kwargs)
        stage = strategy.sharding_stage
        shard_size = mesh.shape.get("sharding", 1)
        k_steps = int(strategy.gradient_merge_configs.get("k_steps", 1)) \
            if strategy.gradient_merge else 1
        opt = self.optimizer
        param_specs = self._param_specs

        def grad_constraint(grads):
            # ZeRO-2: pin grads sharded over "sharding" → XLA reduce-scatters
            # instead of all-reducing (reference GroupShardedStage2:49).
            if stage < 2 or shard_size <= 1:
                return grads

            def pin(g, pspec):
                # Constrain only rank>=2 grads, and only on dim 0: rank-1
                # grads and inner-dim pins (e.g. the hidden dim of a
                # vocab-parallel embedding grad) save ~no memory but force
                # GSPMD to reshard the full activation-grad feeding the
                # reduce/scatter — the "involuntary full rematerialization"
                # path.  Dim-0 reduce-scatter is the layout XLA can emit
                # directly from the grad dot/scatter.
                spec = list(_pad_spec(tuple(pspec), g.ndim))
                if "sharding" not in spec:
                    if g.ndim < 2 or spec[0] is not None \
                            or g.shape[0] % shard_size != 0:
                        return g
                    spec[0] = "sharding"
                return jax.lax.with_sharding_constraint(
                    g, _named_sharding(mesh, P(*spec)))

            return {n: pin(g, param_specs[n]) for n, g in grads.items()}

        def step_fn(params, opt_state, buffers, key, lr, step, batch):
            if k_steps > 1:
                def micro(carry, idx_mb):
                    i, mb = idx_mb
                    acc, bufs = carry
                    (loss, bufs), grads = jax.value_and_grad(
                        pure_loss, has_aux=True)(
                        params, bufs, jax.random.fold_in(key, i), mb)
                    return (jax.tree_util.tree_map(jnp.add, acc, grads),
                            bufs), loss

                zero = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, buffers), losses = jax.lax.scan(
                    micro, (zero, buffers),
                    (jnp.arange(k_steps),
                     jax.tree_util.tree_map(
                         lambda b: b.reshape((k_steps, b.shape[0] // k_steps)
                                             + b.shape[1:]), batch)))
                grads = jax.tree_util.tree_map(lambda g: g / k_steps, grads)
                loss = losses.mean()
            else:
                (loss, buffers), grads = jax.value_and_grad(
                    pure_loss, has_aux=True)(params, buffers, key, batch)
            grads = grad_constraint(grads)
            with jax.named_scope("optimizer"):
                new_params, new_state = opt.functional_update(
                    params, grads, opt_state, lr=lr, step=step)
            # keep parameter layout stable across steps
            new_params = {
                n: jax.lax.with_sharding_constraint(
                    a, _named_sharding(mesh, param_specs[n]))
                for n, a in new_params.items()}
            return new_params, new_state, buffers, loss

        param_sh = _tree_shardings(mesh, param_specs)
        opt_sh = jax.tree_util.tree_map(
            lambda s: self._opt_sharding(s), self._opt_specs,
            is_leaf=lambda x: isinstance(x, P))
        batch_sh = self._batch_shardings(batch_sig)
        rep = _named_sharding(mesh, P())
        buf_sh = {n: rep for n in self.buffers}
        donate = (0, 1, 2) if self.donate else ()
        return jax.jit(
            step_fn,
            in_shardings=(param_sh, opt_sh, buf_sh, rep, rep, rep,
                          batch_sh),
            out_shardings=(param_sh, opt_sh, buf_sh, rep),
            donate_argnums=donate)

    def _batch_shardings(self, batch_sig):
        if self.batch_spec is not None:
            return tuple(_named_sharding(self.mesh, s)
                         for s in self.batch_spec)
        dp_axes = tuple(a for a in ("dp", "sharding")
                        if self.mesh.shape.get(a, 1) > 1)
        spec = P(dp_axes if dp_axes else None)
        return tuple(_named_sharding(self.mesh, spec) for _ in batch_sig)

    # ------------------------------------------------------------- stepping
    def __call__(self, *batch, **static_kwargs):
        return self.step(*batch, **static_kwargs)

    def step(self, *batch, **static_kwargs):
        """Run one training step; returns the loss as a Tensor and keeps
        params/opt state on device in their sharded layout.

        Multi-process jobs (jax.distributed initialized, reference
        multi-trainer fleet run): each process passes its LOCAL batch
        shard — the reference's per-rank reader semantics — and the step
        assembles the global sharded arrays."""
        if self.opt_state is None:
            self._init_opt_state()
        arrays = batch_arrays(batch)
        if jax.process_count() > 1:
            arrays = self._globalize_batch(arrays)
        sig = batch_signature(arrays, static_kwargs)
        fn = self._cache.get(sig)
        first_call = fn is None
        if first_call:
            fn = self._build(arrays, static_kwargs)
            self._cache[sig] = fn
        self._step_count += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = prandom.next_key()
        # the compiled call as one host span of the profiler's trace,
        # carrying the step number (a no-op without a profiler session)
        t0 = time.perf_counter() if first_call else 0.0
        with jax.profiler.StepTraceAnnotation("fleet.train_step",
                                              step_num=self._step_count):
            self.params, self.opt_state, self.buffers, loss = fn(
                self.params, self.opt_state, self.buffers, key, lr,
                jnp.asarray(self._step_count, jnp.int32), arrays)
        if first_call:
            # a new batch signature's first call traces and compiles:
            # one CompileLog event, as to_static and the serving
            # programs leave one
            get_compile_log().record(
                "fleet-train-step", sig, signature_of(arrays),
                time.perf_counter() - t0)
        lr_scheduler_tick(self.optimizer)
        return Tensor(loss)

    def _globalize_batch(self, arrays):
        """Per-process local shards -> global arrays over the mesh (the
        TCPStore-less multi-host path: jax.distributed's coordination
        service already rendezvoused the processes)."""
        import numpy as _np

        sig = tuple((a.shape, str(a.dtype)) for a in arrays)
        shardings = self._batch_shardings(sig)
        return tuple(
            jax.make_array_from_process_local_data(sh, _np.asarray(a))
            for sh, a in zip(shardings, arrays))

    def _compiled_executable(self, batch, static_kwargs):
        """The compiled executable serving this batch signature (must have
        been stepped once; jax caches the lower+compile)."""
        arrays = batch_arrays(batch)
        sig = batch_signature(arrays, static_kwargs)
        fn = self._cache.get(sig)
        if fn is None:
            raise RuntimeError("step this batch signature once first")
        return fn.lower(
            self.params, self.opt_state, self.buffers, prandom.next_key(),
            jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32),
            arrays).compile()

    def cost_analysis(self, *batch, **static_kwargs):
        """XLA's per-step cost analysis (flops, bytes accessed) — the
        compiler-derived backing for MFU claims (vs the hand 6·N·T
        arithmetic)."""
        return self._compiled_executable(batch, static_kwargs) \
            .cost_analysis()

    def compiled_text(self, *batch, **static_kwargs) -> str:
        """The compiled step's module text — where a bring-up check
        reads which kernels (``tpu_custom_call``) and collectives the
        compiler actually put into the step."""
        return self._compiled_executable(batch, static_kwargs).as_text()

    def memory_analysis(self, *batch, **static_kwargs):
        """XLA's compiled-executable memory breakdown (temp/argument/output
        bytes) — the compiler-reported peak-buffer backing for pipeline
        schedule memory claims (docs/PIPELINE.md)."""
        return self._compiled_executable(batch, static_kwargs) \
            .memory_analysis()

    # ------------------------------------------------------------ state i/o
    def sync_params_to_model(self):
        """Write the (gathered) device params back into the eager Layer —
        for checkpointing via the normal state_dict path."""
        for n, p in self._param_info:
            p._data = jnp.asarray(self.params[n])
        for n, b in self._buffer_info:
            b._data = jnp.asarray(self.buffers[n])
        return self.model

    def state_dict(self):
        self.sync_params_to_model()
        return self.model.state_dict()
