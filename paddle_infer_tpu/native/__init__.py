"""Native runtime bindings (ctypes over native/libpitnative.so).

The C++ side provides the pieces the reference implements natively and a
Python loop cannot serve fast enough:
  - MultiSlotDataFeed — threaded slot-text parsing + shuffle + batch
    assembly (reference framework/data_feed.cc).
  - KVBlockPool — paged KV-cache page tables with copy-on-write forks
    (reference CacheKV buffers + allocator stack; consumed by the paged
    attention serving path).
  - TensorStore — mmap'd raw-tensor checkpoint format (reference
    .pdiparams raw serialization, inference/io.cc), zero-copy reads.

The library is built on first use from the files git tracks (g++ and
make only — no external deps): ``ensure_built`` keys staleness on a hash
of the sources and serializes concurrent first users on a file lock.
``available()`` reports whether the native path is up; a failed build
says why once, with make's stderr.
"""
from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import shutil
import subprocess
import tempfile
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")

# numpy dtype <-> stable wire codes for TensorStore
_DTYPE_CODES = {
    "float32": 0, "float64": 1, "float16": 2, "bfloat16": 3,
    "int8": 4, "uint8": 5, "int16": 6, "int32": 7, "int64": 8, "bool": 9,
}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


def _np_dtype(name: str) -> np.dtype:
    if name == "bfloat16":     # numpy needs ml_dtypes for bf16
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


_lib = None
_load_error: Optional[str] = None
_build_status: Optional[str] = None     # "found" | "built" once resolved


def _source_digest() -> str:
    """sha256 over the Makefile and every .cc/.h, by name order — what
    the built libraries are a function of.  Recorded beside each library
    so staleness never depends on mtimes (a fresh checkout or copy has
    them equal or shuffled)."""
    h = hashlib.sha256()
    for name in sorted(os.listdir(_NATIVE_DIR)):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            h.update(name.encode() + b"\0")
            with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _up_to_date(lib_path: str, digest: str) -> bool:
    try:
        with open(lib_path + ".sha256") as f:
            return f.read().strip() == digest and os.path.exists(lib_path)
    except OSError:
        return False


def ensure_built(lib_name: str = "libpitnative.so") -> Tuple[str, str]:
    """Path of ``native/<lib_name>`` built from the sources as they are
    now, and whether it was ``"found"`` or ``"built"``.  Concurrent first
    users (xdist workers, a parent's children) serialize on an exclusive
    file lock: one builds — into a private directory, then ``os.replace``
    into place — and the rest find it.  Raises RuntimeError carrying
    make's stderr when the build fails."""
    lib_path = os.path.join(_NATIVE_DIR, lib_name)
    digest = _source_digest()
    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _up_to_date(lib_path, digest):
            return lib_path, "found"
        out = tempfile.mkdtemp(prefix=".build-", dir=_NATIVE_DIR)
        try:
            proc = subprocess.run(
                ["make", "-C", _NATIVE_DIR, "-j4", f"OUT={out}",
                 os.path.join(out, lib_name)],
                capture_output=True, text=True, timeout=300)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"native build of {lib_name} failed "
                    f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
            os.replace(os.path.join(out, lib_name), lib_path)
            stamp = os.path.join(out, "stamp")
            with open(stamp, "w") as f:
                f.write(digest)
            os.replace(stamp, lib_path + ".sha256")
        finally:
            shutil.rmtree(out, ignore_errors=True)
    return lib_path, "built"


def build_status() -> Optional[str]:
    """``"found"`` / ``"built"`` once the library is loaded, else None."""
    return _build_status


def _load():
    global _lib, _load_error, _build_status
    if _lib is not None:
        return _lib
    if _load_error is not None:
        return None            # failure latched: don't re-spawn make
    try:
        lib_path, status = ensure_built()
        lib = ctypes.CDLL(lib_path)
    except (OSError, RuntimeError, subprocess.TimeoutExpired) as e:
        _load_error = str(e)
        # said once, with make's own words: callers that only ask
        # available() would otherwise never learn why it is False
        warnings.warn(f"native runtime unavailable: {_load_error}",
                      RuntimeWarning, stacklevel=2)
        return None
    c = ctypes
    sigs = {
        # datafeed
        "datafeed_create": ([c.POINTER(c.c_char_p), c.c_int32,
                             c.POINTER(c.c_uint8), c.c_int32, c.c_int32,
                             c.c_int32, c.c_int32, c.c_uint64,
                             c.POINTER(c.c_int32)], c.c_void_p),
        "datafeed_destroy": ([c.c_void_p], None),
        "datafeed_size": ([c.c_void_p], c.c_int64),
        "datafeed_reset": ([c.c_void_p, c.c_uint64], None),
        "datafeed_next": ([c.c_void_p], c.c_int32),
        "datafeed_slot_len": ([c.c_void_p, c.c_int32], c.c_int64),
        "datafeed_slot_float": ([c.c_void_p, c.c_int32],
                                c.POINTER(c.c_float)),
        "datafeed_slot_int": ([c.c_void_p, c.c_int32],
                              c.POINTER(c.c_int64)),
        "datafeed_slot_lod": ([c.c_void_p, c.c_int32],
                              c.POINTER(c.c_int64)),
        "datafeed_slot_lod_len": ([c.c_void_p, c.c_int32], c.c_int64),
        # kv allocator
        "kv_pool_create": ([c.c_int32, c.c_int32], c.c_void_p),
        "kv_pool_destroy": ([c.c_void_p], None),
        "kv_pool_free_blocks": ([c.c_void_p], c.c_int32),
        "kv_seq_reserve": ([c.c_void_p, c.c_int64, c.c_int32], c.c_int32),
        "kv_seq_table": ([c.c_void_p, c.c_int64, c.POINTER(c.c_int32),
                          c.c_int32], c.c_int32),
        "kv_seq_length": ([c.c_void_p, c.c_int64], c.c_int32),
        "kv_seq_fork": ([c.c_void_p, c.c_int64, c.c_int64], c.c_int32),
        "kv_seq_cow_last": ([c.c_void_p, c.c_int64, c.POINTER(c.c_int32),
                             c.POINTER(c.c_int32)], c.c_int32),
        "kv_seq_free": ([c.c_void_p, c.c_int64], None),
        "kv_block_alloc": ([c.c_void_p], c.c_int32),
        "kv_block_ref": ([c.c_void_p, c.c_int32], c.c_int32),
        "kv_block_unref": ([c.c_void_p, c.c_int32], c.c_int32),
        "kv_block_refcount": ([c.c_void_p, c.c_int32], c.c_int32),
        "kv_seq_assign": ([c.c_void_p, c.c_int64, c.POINTER(c.c_int32),
                           c.c_int32, c.c_int32], c.c_int32),
        # tensor store
        "tstore_writer_open": ([c.c_char_p], c.c_void_p),
        "tstore_writer_add": ([c.c_void_p, c.c_char_p, c.c_uint32,
                               c.POINTER(c.c_int64), c.c_uint32,
                               c.c_void_p, c.c_uint64], c.c_int32),
        "tstore_writer_close": ([c.c_void_p], c.c_int32),
        "tstore_reader_open": ([c.c_char_p], c.c_void_p),
        "tstore_reader_close": ([c.c_void_p], None),
        "tstore_reader_count": ([c.c_void_p], c.c_int32),
        "tstore_entry_name": ([c.c_void_p, c.c_int32], c.c_char_p),
        "tstore_entry_dtype": ([c.c_void_p, c.c_int32], c.c_uint32),
        "tstore_entry_ndim": ([c.c_void_p, c.c_int32], c.c_uint32),
        "tstore_entry_dims": ([c.c_void_p, c.c_int32],
                              c.POINTER(c.c_int64)),
        "tstore_entry_nbytes": ([c.c_void_p, c.c_int32], c.c_uint64),
        "tstore_entry_data": ([c.c_void_p, c.c_int32], c.c_void_p),
        "tstore_last_error": ([], c.c_int32),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    _build_status = status
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


# ------------------------------------------------------------- data feed
class MultiSlotDataFeed:
    """Threaded multi-slot text reader (reference MultiSlotDataFeed,
    framework/data_feed.h:1572).

    ``slots``: list of (name, kind) with kind "float" (dense values) or
    "int" (sparse id list).  Iterating yields dicts
    name -> (values ndarray, lod ndarray[batch+1]).
    """

    def __init__(self, files: Sequence[str], slots: Sequence[Tuple[str, str]],
                 batch_size: int = 32, num_threads: int = 4,
                 shuffle: bool = False, seed: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native runtime unavailable: {_load_error}")
        self._lib = lib
        self._slots = list(slots)
        self._epoch = 0
        self._seed = seed
        self._iterating = False
        arr = (ctypes.c_char_p * len(files))(
            *[os.fsencode(f) for f in files])
        flags = (ctypes.c_uint8 * len(slots))(
            *[1 if kind == "float" else 0 for _, kind in slots])
        err = ctypes.c_int32(0)
        self._h = lib.datafeed_create(arr, len(files), flags, len(slots),
                                      batch_size, num_threads,
                                      1 if shuffle else 0, seed,
                                      ctypes.byref(err))
        if not self._h:
            if err.value == 1:
                raise FileNotFoundError(
                    f"datafeed: cannot open one of {list(files)}")
            raise ValueError("datafeed: malformed slot record")

    def __len__(self):
        return int(self._lib.datafeed_size(self._h))

    def __iter__(self):
        # the native cursor and batch buffers are shared per feed: two live
        # iterators would silently interleave and corrupt each other's
        # batch stream (e.g. zip(feed, feed), or an eval pass inside an
        # epoch) — refuse instead
        if self._iterating:
            raise RuntimeError(
                "MultiSlotDataFeed supports one live iterator at a time; "
                "finish (or discard) the previous epoch's iterator first")
        self._iterating = True
        try:
            self._lib.datafeed_reset(self._h, self._seed + self._epoch)
            self._epoch += 1
            while True:
                n = self._lib.datafeed_next(self._h)
                if n <= 0:
                    return
                out: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
                for i, (name, kind) in enumerate(self._slots):
                    ln = self._lib.datafeed_slot_len(self._h, i)
                    if kind == "float":
                        ptr = self._lib.datafeed_slot_float(self._h, i)
                        vals = np.ctypeslib.as_array(ptr, (ln,)).copy() \
                            if ln else np.empty((0,), np.float32)
                    else:
                        ptr = self._lib.datafeed_slot_int(self._h, i)
                        vals = np.ctypeslib.as_array(ptr, (ln,)).copy() \
                            if ln else np.empty((0,), np.int64)
                    lod_len = self._lib.datafeed_slot_lod_len(self._h, i)
                    lod_ptr = self._lib.datafeed_slot_lod(self._h, i)
                    lod = np.ctypeslib.as_array(lod_ptr, (lod_len,)).copy()
                    out[name] = (vals, lod)
                yield out
        finally:
            self._iterating = False

    def __del__(self):
        h = getattr(self, "_h", None)
        if h and self._lib is not None:
            self._lib.datafeed_destroy(h)
            self._h = None


# --------------------------------------------------------- kv block pool
class KVBlockPool:
    """Paged-KV page-table manager (native, O(1) per decode step).

    Mirrors a device-side pool [num_blocks, block_size, heads, head_dim]:
    this object only tracks which blocks belong to which sequence; the
    arrays live in HBM and are indexed by the tables this hands out
    (serving engine + ops/pallas paged attention consume them).
    """

    def __init__(self, num_blocks: int, block_size: int):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native runtime unavailable: {_load_error}")
        self._lib = lib
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._h = lib.kv_pool_create(num_blocks, block_size)
        if not self._h:
            raise ValueError("kv_pool_create failed")

    @property
    def free_blocks(self) -> int:
        return int(self._lib.kv_pool_free_blocks(self._h))

    def reserve(self, seq_id: int, num_tokens: int) -> int:
        """Grow seq to hold num_tokens; returns block count.
        Raises MemoryError when the pool is exhausted."""
        n = self._lib.kv_seq_reserve(self._h, seq_id, num_tokens)
        if n < 0:
            raise MemoryError(
                f"KV pool exhausted ({self.num_blocks} blocks)")
        return int(n)

    def block_table(self, seq_id: int) -> np.ndarray:
        cap = self.num_blocks
        buf = (ctypes.c_int32 * cap)()
        n = self._lib.kv_seq_table(self._h, seq_id, buf, cap)
        return np.ctypeslib.as_array(buf)[:n].copy()

    def length(self, seq_id: int) -> int:
        return int(self._lib.kv_seq_length(self._h, seq_id))

    def fork(self, parent: int, child: int) -> int:
        """Copy-on-write fork (beam search)."""
        n = self._lib.kv_seq_fork(self._h, parent, child)
        if n < 0:
            raise KeyError(f"unknown parent sequence {parent}")
        return int(n)

    def cow_last_block(self, seq_id: int) -> Optional[Tuple[int, int]]:
        """If seq's last block is shared, allocate a private copy; returns
        (src_block, dst_block) for the caller to issue the device copy, or
        None when the block was already exclusive."""
        src = ctypes.c_int32()
        dst = ctypes.c_int32()
        rc = self._lib.kv_seq_cow_last(self._h, seq_id,
                                       ctypes.byref(src), ctypes.byref(dst))
        if rc < 0:
            raise MemoryError("cow failed (unknown seq or pool exhausted)")
        return (int(src.value), int(dst.value)) if rc == 1 else None

    def free(self, seq_id: int):
        self._lib.kv_seq_free(self._h, seq_id)

    # ---- block-level ops (prefix cache: direct refs on retained blocks,
    # independent of any live sequence) ----
    def alloc_block(self) -> int:
        """Allocate one block outside any sequence (refcount 1)."""
        b = self._lib.kv_block_alloc(self._h)
        if b < 0:
            raise MemoryError(
                f"KV pool exhausted ({self.num_blocks} blocks)")
        return int(b)

    def ref_block(self, block: int) -> int:
        """Take an extra reference on a live block; returns the new
        refcount.  Ref'ing a free block raises (double-free guard)."""
        rc = self._lib.kv_block_ref(self._h, block)
        if rc < 0:
            raise ValueError(f"ref of free/out-of-range block {block}")
        return int(rc)

    def unref_block(self, block: int) -> int:
        """Drop one reference (block returns to the free list at zero);
        returns the new refcount.  Unref'ing a free block raises."""
        rc = self._lib.kv_block_unref(self._h, block)
        if rc < 0:
            raise ValueError(f"unref of free/out-of-range block {block}")
        return int(rc)

    def block_refcount(self, block: int) -> int:
        """Current refcount (0 = free).  Test/diagnostic introspection."""
        rc = self._lib.kv_block_refcount(self._h, block)
        if rc < 0:
            raise ValueError(f"block {block} out of range")
        return int(rc)

    def assign(self, seq_id: int, blocks, num_tokens: int) -> int:
        """Replace ``seq_id``'s table with ``blocks`` (each ref'd; the
        sequence's previous blocks are released) and set its length to
        ``num_tokens``.  ``reserve`` grows from here without touching
        the assigned prefix."""
        blocks = [int(b) for b in blocks]
        arr = (ctypes.c_int32 * len(blocks))(*blocks)
        n = self._lib.kv_seq_assign(self._h, seq_id, arr, len(blocks),
                                    num_tokens)
        if n < 0:
            raise ValueError(f"assign with free/out-of-range block in "
                             f"{blocks}")
        return int(n)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h and self._lib is not None:
            self._lib.kv_pool_destroy(h)
            self._h = None


# ---------------------------------------------------------- tensor store
def save_tensors(path: str, tensors: Dict[str, np.ndarray]):
    """Write named arrays to the raw binary store (reference .pdiparams)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native runtime unavailable: {_load_error}")
    h = lib.tstore_writer_open(os.fsencode(path))
    if not h:
        raise OSError(f"cannot open {path} for writing")
    try:
        for name, arr in tensors.items():
            arr = np.ascontiguousarray(arr)
            dt = str(arr.dtype)
            if dt not in _DTYPE_CODES:
                raise TypeError(f"unsupported dtype {dt} for '{name}'")
            dims = (ctypes.c_int64 * arr.ndim)(*arr.shape)
            rc = lib.tstore_writer_add(
                h, name.encode(), _DTYPE_CODES[dt], dims, arr.ndim,
                arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes)
            if rc != 0:
                raise OSError(f"write failed for '{name}'")
    finally:
        if lib.tstore_writer_close(h) != 0:
            raise OSError(f"close failed for {path}")


def load_tensors(path: str) -> Dict[str, np.ndarray]:
    """mmap the store and return zero-copy array views (copy() them if the
    file may be replaced while in use)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native runtime unavailable: {_load_error}")
    h = lib.tstore_reader_open(os.fsencode(path))
    if not h:
        # corrupt-but-present must not masquerade as missing: the auto
        # checkpoint restore path treats FileNotFoundError as "no
        # checkpoint yet" and would silently start from scratch
        if lib.tstore_last_error() == 2:
            raise ValueError(f"corrupt/truncated tensor store {path}")
        raise FileNotFoundError(f"cannot open tensor store {path}")
    out: Dict[str, np.ndarray] = {}
    try:
        n = lib.tstore_reader_count(h)
        for i in range(n):
            name = lib.tstore_entry_name(h, i).decode()
            dtype = _np_dtype(_CODE_DTYPES[lib.tstore_entry_dtype(h, i)])
            ndim = lib.tstore_entry_ndim(h, i)
            dims_ptr = lib.tstore_entry_dims(h, i)
            shape = tuple(dims_ptr[d] for d in range(ndim))
            nbytes = lib.tstore_entry_nbytes(h, i)
            data = lib.tstore_entry_data(h, i)
            buf = (ctypes.c_char * nbytes).from_address(data)
            # copy: the reader handle is closed before returning
            out[name] = np.frombuffer(buf, dtype=dtype).reshape(shape).copy()
    finally:
        lib.tstore_reader_close(h)
    return out


__all__ = ["available", "MultiSlotDataFeed", "KVBlockPool",
           "save_tensors", "load_tensors"]
