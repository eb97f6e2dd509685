"""Collective communication over mesh axes.

Reference surface: python/paddle/distributed/collective.py (all_reduce :639,
all_gather :889, alltoall :1229, reduce_scatter :1858, broadcast, send/recv)
backed by paddle/fluid/distributed/collective/ProcessGroupNCCL.cc.

TPU-first redesign: a "process group" is a ``Group(mesh, axis)``; every
collective is a ``shard_map``-wrapped ``jax.lax`` collective compiled by XLA
onto ICI/DCN — there is no hand-rolled transport.  Inputs/outputs are global
``jax.Array``s (or framework Tensors): an array *sharded* over the group axis
is the analog of "each rank holds its shard"; a *replicated* array is "each
rank holds a copy".  All functions are pure and differentiable, so the same
code path serves eager calls and traced train-step programs.

Process-rendezvous (the reference's TCPStore, distributed/store/tcp_store.h)
maps to ``jax.distributed.initialize`` — see distributed/env.py.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core.tensor import Tensor
from . import topology


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = (mesh, axis name(s)).

    Reference: paddle.distributed.Group / ProcessGroup.h:53 — but where the
    reference materialises an NCCL communicator, this is just a name XLA
    resolves to ICI neighbours at compile time.
    """

    def __init__(self, mesh: Mesh, axis: Union[str, Sequence[str]]):
        self.mesh = mesh
        self.axis = tuple(axis) if not isinstance(axis, str) else (axis,)

    @property
    def nranks(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axis]))

    world_size = nranks

    @property
    def name(self):
        return "+".join(self.axis)

    def __repr__(self):
        return f"Group(axis={self.axis}, nranks={self.nranks})"

    def __hash__(self):
        return hash((self.mesh, self.axis))

    def __eq__(self, other):
        return (isinstance(other, Group) and self.mesh == other.mesh
                and self.axis == other.axis)


def _default_group() -> Group:
    hcg = topology.get_hybrid_communicate_group()
    if hcg is not None:
        return Group(hcg.mesh, "dp")
    mesh = topology.get_current_mesh()
    if mesh is None:
        raise RuntimeError(
            "no communication group: call fleet.init / set_current_mesh "
            "first, or pass group= explicitly")
    return Group(mesh, mesh.axis_names[0])


def _axis(group):
    ax = group.axis
    return ax[0] if len(ax) == 1 else ax


def _as_array(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


def _wrap_like(out, x):
    return Tensor(out) if isinstance(x, Tensor) else out


# ------------------------------------------------ quantized all-reduce
# EQuARX-style blockwise int8 all-reduce (PAPERS.md): flatten, split into
# fixed-size blocks, scale each block by maxabs/127, ship int8 payload +
# one fp32 scale per block.  Two stages (quantized reduce-scatter shard
# ownership + quantized all-gather of the reduced shards) when the block
# count divides the group size; otherwise a one-stage quantized
# gather-reduce with the exact output shape (the "exact-shape fallback").

_Q8_BLOCK = 256          # elements per quantization block
_Q8_SCALE_BYTES = 4      # one fp32 scale per block on the wire


def _q8_encode(blocks):
    """[nb, block] f32 -> (int8 codes, fp32 scales [nb, 1])."""
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0.0, scale, 1.0)   # all-zero block: scale 1
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantized_psum(x, axis, nranks: int, block: int = _Q8_BLOCK):
    """Blockwise-int8 SUM all-reduce of ``x`` over mesh ``axis``, callable
    inside any shard_map body (``ops.distributed.mp_quant_matmul`` reuses
    it for the row-parallel serving matmuls).  Exact shape in, exact
    shape out; only the wire format is quantized."""
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    nb = -(-n // block)
    flat = jnp.pad(flat, (0, nb * block - n))
    q, s = _q8_encode(flat.reshape(nb, block))
    gq = jax.lax.all_gather(q, axis)          # [r, nb, block] int8 wire
    gs = jax.lax.all_gather(s, axis)          # [r, nb, 1] fp32 scales
    if nb % nranks == 0:
        # stage 1: each rank dequant-reduces only its 1/r shard of the
        # blocks (reduce-scatter ownership), then requantizes the sum
        shard = nb // nranks
        idx = jax.lax.axis_index(axis)
        myq = jax.lax.dynamic_slice_in_dim(gq, idx * shard, shard, axis=1)
        mys = jax.lax.dynamic_slice_in_dim(gs, idx * shard, shard, axis=1)
        red = jnp.sum(myq.astype(jnp.float32) * mys, axis=0)
        q2, s2 = _q8_encode(red)
        # stage 2: all-gather the reduced int8 shards back to full blocks
        outq = jax.lax.all_gather(q2, axis, tiled=True)
        outs = jax.lax.all_gather(s2, axis, tiled=True)
        vals = outq.astype(jnp.float32) * outs
    else:
        # exact-shape fallback: block count doesn't divide the group, so
        # skip the scatter stage and dequant-sum the full gather
        vals = jnp.sum(gq.astype(jnp.float32) * gs, axis=0)
    return vals.reshape(-1)[:n].reshape(shape).astype(dtype)


def quantized_wire_bytes(n_elems: int, nranks: int, itemsize: int = 4,
                         block: int = _Q8_BLOCK):
    """(quantized_bytes, full_precision_bytes) moved per rank by one
    SUM all-reduce of ``n_elems`` elements over ``nranks`` ranks,
    analytic ring model: 2(r-1)/r of the payload crosses the wire."""
    nranks = max(int(nranks), 1)
    ring = 2.0 * (nranks - 1) / nranks
    nb = -(-int(n_elems) // block)
    q_payload = nb * block * 1 + nb * _Q8_SCALE_BYTES
    fp_payload = int(n_elems) * int(itemsize)
    return ring * q_payload, ring * fp_payload


def quantization_error_bound(parts, block: int = _Q8_BLOCK) -> float:
    """Worst-case elementwise |quantized - exact| for summing the
    per-rank contributions ``parts`` (host arrays, same shape) through
    ``quantized_psum``.  Stage 1 rounds each rank's block at most
    maxabs/254 (= scale/2); stage 2 re-rounds the reduced block once
    more.  The one-stage fallback only incurs stage 1, so this bound
    covers both paths."""
    flats = [np.asarray(p, np.float32).reshape(-1) for p in parts]
    n = flats[0].shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    amax_sum = np.zeros(nb, np.float64)
    for f in flats:
        fb = np.pad(f, (0, pad)).reshape(nb, block)
        amax_sum += np.max(np.abs(fb), axis=1)
    stage1 = amax_sum / 254.0
    stage2 = (amax_sum + stage1) / 254.0
    return float(np.max(stage1 + stage2)) if nb else 0.0


class CollectiveLedger:
    """Thread-safe analytic tally of interconnect bytes moved by
    collectives, by op and wire dtype, plus bytes saved by quantized
    wire formats vs their full-precision equivalent.  Feeds the
    ``collective_bytes_total{op,dtype}`` / ``collective_bytes_saved_total``
    Prometheus families through the serving snapshot."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self._by: Dict[str, Dict[str, float]] = {}
            self._saved = 0.0
            self._calls = 0

    def record(self, op: str, dtype: str, nbytes: float,
               saved: float = 0.0):
        with self._lock:
            per_op = self._by.setdefault(str(op), {})
            per_op[str(dtype)] = per_op.get(str(dtype), 0.0) + float(nbytes)
            self._saved += float(saved)
            self._calls += 1

    def snapshot(self) -> Dict:
        with self._lock:
            by = {op: dict(d) for op, d in self._by.items()}
            total = sum(v for d in by.values() for v in d.values())
            return {"calls": self._calls,
                    "by_op_dtype": by,
                    "bytes_total": total,
                    "bytes_saved_total": self._saved}


LEDGER = CollectiveLedger()


def _record_wire(op: str, arr, group: Group, factor: float):
    """Analytic wire bytes for one full-precision collective: ``factor``
    × global payload (ring model; e.g. all-reduce 2(r-1)/r)."""
    nbytes = float(arr.size) * np.dtype(arr.dtype).itemsize
    LEDGER.record(op, str(np.dtype(arr.dtype)), factor * nbytes)


def _ring(group: Group) -> float:
    r = max(group.nranks, 1)
    return (r - 1) / r


# Each collective body is built once per (mesh, axis, variant) and jitted;
# shard_map partitions over the group axis and leaves every other mesh axis
# replicated, so these compose with hybrid meshes.
@functools.lru_cache(maxsize=None)
def _build(mesh: Mesh, axis, kind: str, **kw):
    full = P(axis)          # sharded on dim 0 over the group axis
    rep = P()

    def smap(fn, in_spec, out_spec):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_spec, check_vma=False))

    if kind == "allreduce":
        op = kw["op"]

        def body(x):
            if op == ReduceOp.SUM:
                return jax.lax.psum(x, axis)
            if op == ReduceOp.MAX:
                return jax.lax.pmax(x, axis)
            if op == ReduceOp.MIN:
                return jax.lax.pmin(x, axis)
            if op == ReduceOp.AVG:
                return jax.lax.pmean(x, axis)
            if op == ReduceOp.PROD:
                gathered = jax.lax.all_gather(x, axis)
                return jnp.prod(gathered, axis=0)
            raise ValueError(op)

        return smap(body, (rep,), rep)

    if kind == "allreduce_q8":
        nranks, block = kw["nranks"], kw["block"]
        return smap(lambda x: quantized_psum(x, axis, nranks, block),
                    (rep,), rep)

    if kind == "allreduce_sharded":
        # input sharded over axis on dim0 → reduce shards → replicated
        return smap(lambda x: jax.lax.psum(x, axis), (full,), rep)

    if kind == "allgather":
        # input sharded on dim 0 over the group axis; output replicated with
        # shards concatenated along ``gather_axis`` (tiled all_gather).
        ga = kw["gather_axis"]
        if ga == 0:
            return smap(lambda x: jax.lax.all_gather(x, axis, tiled=True),
                        (full,), rep)

        def body(x):
            return jax.lax.all_gather(x, axis, axis=ga, tiled=True)

        return smap(body, (full,), rep)

    if kind == "reducescatter":
        # replicated input (each rank holds the full array) → reduce across
        # ranks, each keeps its 1/N slice: output sharded on dim 0.
        return smap(
            lambda x: jax.lax.psum_scatter(x, axis, tiled=True),
            (rep,), full)

    if kind == "broadcast":
        src = kw["src"]

        def body(x):
            idx = jax.lax.axis_index(axis)
            val = jnp.where(idx == src, x, jnp.zeros_like(x))
            return jax.lax.psum(val, axis)

        return smap(body, (full,), full)

    if kind == "alltoall":
        # input sharded on dim 0; each shard's dim 0 is further split into
        # nranks chunks exchanged pairwise (NCCL AllToAll semantics).
        def body(x):
            n = jax.lax.psum(1, axis)
            xs = x.reshape((n, x.shape[0] // n) + x.shape[1:])
            out = jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=0,
                                     tiled=False)
            return out.reshape(x.shape)

        return smap(body, (full,), full)

    if kind == "ppermute":
        perm = tuple(kw["perm"])
        return smap(lambda x: jax.lax.ppermute(x, axis, perm=perm),
                    (full,), full)

    if kind == "p2p":
        # point-to-point: dst's shard becomes src's shard, everyone else
        # keeps their data (reference send/recv pair semantics).
        src, dst = kw["src"], kw["dst"]

        def body(x):
            y = jax.lax.ppermute(x, axis, perm=[(src, dst)])
            idx = jax.lax.axis_index(axis)
            return jnp.where(idx == dst, y, x)

        return smap(body, (full,), full)

    if kind == "reduce":
        op, dst = kw["op"], kw["dst"]

        def body(x):
            if op == ReduceOp.SUM:
                red = jax.lax.psum(x, axis)
            elif op == ReduceOp.MAX:
                red = jax.lax.pmax(x, axis)
            elif op == ReduceOp.MIN:
                red = jax.lax.pmin(x, axis)
            else:
                raise ValueError(op)
            idx = jax.lax.axis_index(axis)
            return jnp.where(idx == dst, red, x)

        return smap(body, (full,), full)

    raise ValueError(kind)


# ------------------------------------------------------------------- API

def all_reduce(tensor, op: str = ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True, quantized: Optional[str] = None,
               block: int = _Q8_BLOCK):
    """AllReduce a replicated tensor over the group axis
    (reference: collective.py:639 → ProcessGroupNCCL AllReduce).

    ``quantized="int8"`` switches the wire format to the blockwise-scaled
    int8 reduce-scatter + all-gather (SUM only, single mesh axis); the
    result is approximate within ``quantization_error_bound`` but moves
    ~4x fewer interconnect bytes."""
    group = group or _default_group()
    arr = _as_array(tensor)
    axis = _axis(group)
    if quantized is None:
        out = _build(group.mesh, axis, "allreduce", op=op)(arr)
        _record_wire("all_reduce", arr, group, 2.0 * _ring(group))
    else:
        if quantized != "int8":
            raise ValueError(
                f"unsupported quantized wire format {quantized!r}; "
                "only 'int8' is implemented")
        if op != ReduceOp.SUM:
            raise ValueError("quantized all_reduce supports ReduceOp.SUM only")
        if not isinstance(axis, str):
            raise ValueError(
                "quantized all_reduce needs a single-axis group, got "
                f"axes {group.axis}")
        out = _build(group.mesh, axis, "allreduce_q8",
                     nranks=group.nranks, block=int(block))(arr)
        qb, fp = quantized_wire_bytes(arr.size, group.nranks,
                                      np.dtype(arr.dtype).itemsize,
                                      int(block))
        LEDGER.record("all_reduce", "int8", qb, saved=max(fp - qb, 0.0))
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def all_gather(tensor, group: Optional[Group] = None, axis: int = 0):
    """Gather shards (dim-0-sharded global array) → replicated concat
    (reference: collective.py:889)."""
    group = group or _default_group()
    arr = _as_array(tensor)
    out = _build(group.mesh, _axis(group), "allgather", gather_axis=axis)(arr)
    _record_wire("all_gather", arr, group, _ring(group))
    return _wrap_like(out, tensor)


def reduce_scatter(tensor, op: str = ReduceOp.SUM,
                   group: Optional[Group] = None):
    """Reduce then keep 1/N slice per rank (reference: collective.py:1858)."""
    group = group or _default_group()
    arr = _as_array(tensor)
    out = _build(group.mesh, _axis(group), "reducescatter")(arr)
    _record_wire("reduce_scatter", arr, group, _ring(group))
    return _wrap_like(out, tensor)


def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True):
    """Broadcast rank ``src``'s shard to all (reference: collective.py:639)."""
    group = group or _default_group()
    arr = _as_array(tensor)
    out = _build(group.mesh, _axis(group), "broadcast", src=src)(arr)
    _record_wire("broadcast", arr, group, _ring(group))
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None):
    group = group or _default_group()
    arr = _as_array(tensor)
    out = _build(group.mesh, _axis(group), "reduce", op=op, dst=dst)(arr)
    return _wrap_like(out, tensor)


def alltoall(tensor, group: Optional[Group] = None):
    """Pairwise chunk exchange (reference: collective.py:1229; the transport
    under MoE global_scatter/global_gather)."""
    group = group or _default_group()
    arr = _as_array(tensor)
    out = _build(group.mesh, _axis(group), "alltoall")(arr)
    return _wrap_like(out, tensor)


def ppermute(tensor, perm, group: Optional[Group] = None):
    """Point-to-point ring transfer — the send/recv analog
    (reference: collective.py:1440,1518 send/recv; on TPU p2p is a
    collective_permute over ICI neighbours)."""
    group = group or _default_group()
    arr = _as_array(tensor)
    out = _build(group.mesh, _axis(group), "ppermute",
                 perm=tuple(map(tuple, perm)))(arr)
    return _wrap_like(out, tensor)


def p2p_transfer(tensor, src: int, dst: int, group: Optional[Group] = None):
    """Single src→dst transfer: dst's shard becomes src's, others keep
    theirs — the compiled-SPMD form of a matched send/recv pair
    (reference: ProcessGroup Send/Recv, collective/ProcessGroup.h:53)."""
    group = group or _default_group()
    arr = _as_array(tensor)
    out = _build(group.mesh, _axis(group), "p2p", src=int(src),
                 dst=int(dst))(arr)
    return _wrap_like(out, tensor)


def barrier(group: Optional[Group] = None):
    """Barrier = tiny allreduce (reference: collective.py barrier)."""
    group = group or _default_group()
    all_reduce(jnp.zeros((), jnp.float32), group=group)


def new_group(ranks=None, axis: Union[str, Sequence[str], None] = None
              ) -> Group:
    """Create a group over a mesh axis (reference: collective.py:353).

    The reference takes explicit rank lists; under a named mesh the unit of
    grouping is an axis, so ``axis`` is the native argument.  ``ranks`` is
    accepted for API compat and must correspond to a whole axis.
    """
    hcg = topology.get_hybrid_communicate_group()
    mesh = hcg.mesh if hcg is not None else topology.get_current_mesh()
    if mesh is None:
        raise RuntimeError("fleet.init / set_current_mesh must run first")
    if axis is None:
        axis = mesh.axis_names[0] if ranks is None else _axis_for_ranks(
            mesh, ranks)
    return _register_group(Group(mesh, axis))


def _axis_for_ranks(mesh, ranks):
    topo = topology.CommunicateTopology(list(mesh.axis_names),
                                        [mesh.shape[a] for a in mesh.axis_names])
    for name in mesh.axis_names:
        if sorted(ranks) in [sorted(g) for g in topo.get_comm_list(name)]:
            return name
    raise ValueError(f"ranks {ranks} do not form a mesh-axis group")


# group registry (reference _get_group_map: gid -> Group; gid 0 = world)
_GROUP_REGISTRY = {}


def _register_group(group: Group) -> Group:
    group.id = len(_GROUP_REGISTRY) + 1
    _GROUP_REGISTRY[group.id] = group
    return group


def get_group(gid: int = 0) -> Group:
    if gid == 0:
        g = _default_group()
        g.id = 0          # world group: stable id like registered ones
        return g
    if gid not in _GROUP_REGISTRY:
        raise ValueError(f"no group with id {gid}")
    return _GROUP_REGISTRY[gid]
