"""Native runtime tests: multi-slot data feed (parse/shuffle/batch vs a
Python reference), paged-KV block pool (alloc/fork/CoW/OOM), mmap tensor
store round trip (reference: framework/data_feed.cc, memory/allocation/,
.pdiparams raw serialization)."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from paddle_infer_tpu import native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FIRST_USER = """
import sys
import paddle_infer_tpu.native as n
n._NATIVE_DIR = sys.argv[1]
p = n.KVBlockPool(8, 4)
assert p.reserve(0, 9) == 3 and p.free_blocks == 5
print(n.build_status())
"""


def test_concurrent_first_use_builds_once(tmp_path):
    """Six processes meet a ``native/`` that holds sources only (a fresh
    checkout).  Exactly one builds, the others wait on the lock and find
    the finished library; nobody dlopens a half-written file."""
    src = os.path.join(ROOT, "native")
    ndir = tmp_path / "native"
    ndir.mkdir()
    for name in os.listdir(src):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            shutil.copy(os.path.join(src, name), ndir / name)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", _FIRST_USER, str(ndir)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(6)]
    outs = [p.communicate(timeout=600) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-2000:]
    status = sorted(out.strip() for out, _ in outs)
    assert status == ["built"] + ["found"] * 5, status
    left = sorted(os.listdir(ndir))
    assert not [n for n in left if n.startswith(".build-")
                or n.endswith(".o")], left


def test_staleness_is_source_hash_not_mtime(tmp_path, monkeypatch):
    """Shuffled mtimes (a fresh copy) do not rebuild; a changed source
    does; a failed build reports make's stderr."""
    src = os.path.join(ROOT, "native")
    ndir = tmp_path / "native"
    shutil.copytree(src, ndir, ignore=shutil.ignore_patterns(
        "*.so", "*.o", "*.sha256", ".build*"))
    monkeypatch.setattr(native, "_NATIVE_DIR", str(ndir))
    assert native.ensure_built()[1] == "built"
    lib = ndir / "libpitnative.so"
    os.utime(lib, (1, 1))                  # library "older" than sources
    assert native.ensure_built()[1] == "found"
    with open(ndir / "kv_allocator.cc", "a") as f:
        f.write("\n// touched\n")
    assert native.ensure_built()[1] == "built"
    with open(ndir / "kv_allocator.cc", "a") as f:
        f.write("\n#error broken on purpose\n")
    with pytest.raises(RuntimeError, match="broken on purpose"):
        native.ensure_built()


@pytest.fixture
def slot_files(tmp_path):
    """Two MultiSlot files: slot0 = sparse ids, slot1 = dense floats."""
    rows = []
    rng = np.random.RandomState(0)
    for i in range(23):
        ids = rng.randint(0, 100, rng.randint(1, 5)).tolist()
        feats = rng.rand(3).round(4).tolist()
        rows.append((ids, feats))
    f1 = tmp_path / "part-0.txt"
    f2 = tmp_path / "part-1.txt"
    for path, chunk in ((f1, rows[:12]), (f2, rows[12:])):
        with open(path, "w") as f:
            for ids, feats in chunk:
                f.write(f"{len(ids)} " + " ".join(map(str, ids)) + " "
                        + f"{len(feats)} " + " ".join(map(str, feats))
                        + "\n")
    return [str(f1), str(f2)], rows


class TestDataFeed:
    def test_parse_and_batch(self, slot_files):
        files, rows = slot_files
        feed = native.MultiSlotDataFeed(
            files, [("ids", "int"), ("feat", "float")], batch_size=8,
            num_threads=2, shuffle=False)
        assert len(feed) == 23
        seen_ids, seen_feats = [], []
        batches = 0
        for batch in feed:
            ids, ids_lod = batch["ids"]
            feat, feat_lod = batch["feat"]
            bsz = len(ids_lod) - 1
            assert len(feat_lod) - 1 == bsz
            for b in range(bsz):
                seen_ids.append(ids[ids_lod[b]:ids_lod[b + 1]].tolist())
                seen_feats.append(
                    feat[feat_lod[b]:feat_lod[b + 1]].tolist())
            batches += 1
        assert batches == 3           # 8 + 8 + 7
        want_ids = sorted(ids for ids, _ in rows)
        assert sorted(seen_ids) == want_ids
        np.testing.assert_allclose(
            sorted(np.sum(f) for f in seen_feats),
            sorted(np.sum(f) for _, f in rows), rtol=1e-5)

    def test_shuffle_changes_order_keeps_set(self, slot_files):
        files, rows = slot_files
        feed = native.MultiSlotDataFeed(
            files, [("ids", "int"), ("feat", "float")], batch_size=23,
            shuffle=True, seed=7)
        (ids_a, lod_a) = next(iter(feed))["ids"]
        (ids_b, lod_b) = next(iter(feed))["ids"]   # epoch 2 reshuffles
        assert sorted(ids_a.tolist()) == sorted(ids_b.tolist())
        assert ids_a.tolist() != ids_b.tolist()

    def test_int64_ids_exact(self, tmp_path):
        """Sparse ids beyond double's 2^53 mantissa must survive exactly
        (regression: parse-as-double corruption)."""
        big = 9223372036854775000
        p = tmp_path / "big.txt"
        p.write_text(f"2 {big} 7\n")
        feed = native.MultiSlotDataFeed([str(p)], [("ids", "int")],
                                        batch_size=1)
        ids, lod = next(iter(feed))["ids"]
        assert ids.tolist() == [big, 7]

    def test_threaded_order_deterministic(self, slot_files):
        """Record order must be file-order regardless of thread timing, so
        a seeded shuffle reproduces (regression: completion-order append)."""
        files, rows = slot_files
        runs = []
        for _ in range(3):
            feed = native.MultiSlotDataFeed(
                files, [("ids", "int"), ("feat", "float")], batch_size=23,
                num_threads=4, shuffle=True, seed=5)
            ids, lod = next(iter(feed))["ids"]
            runs.append(ids.tolist())
        assert runs[0] == runs[1] == runs[2]

    def test_bad_record_rejected(self, tmp_path):
        bad = tmp_path / "bad.txt"
        bad.write_text("3 1 2\n")      # claims 3 ids, provides 2
        with pytest.raises(ValueError):
            native.MultiSlotDataFeed([str(bad)], [("ids", "int")])

    def test_absurd_count_rejected_not_bad_alloc(self, tmp_path):
        # a record claiming ~1e11 values must hit the bad-record error
        # path, not throw std::bad_alloc across the C boundary (SIGABRT)
        bad = tmp_path / "absurd.txt"
        bad.write_text("99999999999 1\n")
        with pytest.raises(ValueError):
            native.MultiSlotDataFeed([str(bad)], [("ids", "int")])

    def test_single_live_iterator_enforced(self, slot_files):
        files, _ = slot_files
        feed = native.MultiSlotDataFeed(
            files, [("ids", "int"), ("feat", "float")], batch_size=8)
        it1 = iter(feed)
        next(it1)
        with pytest.raises(RuntimeError):
            next(iter(feed))          # second live iterator: refused
        it1.close()
        assert next(iter(feed))       # released: iteration works again


class TestKVBlockPool:
    def test_reserve_and_table(self):
        pool = native.KVBlockPool(num_blocks=16, block_size=4)
        assert pool.free_blocks == 16
        n = pool.reserve(seq_id=1, num_tokens=9)   # ceil(9/4) = 3 blocks
        assert n == 3
        assert pool.free_blocks == 13
        table = pool.block_table(1)
        assert len(table) == 3 and len(set(table.tolist())) == 3
        assert pool.length(1) == 9
        # growing within the last block allocates nothing
        assert pool.reserve(1, 12) == 3
        assert pool.reserve(1, 13) == 4

    def test_oom_raises(self):
        pool = native.KVBlockPool(num_blocks=2, block_size=4)
        pool.reserve(1, 8)
        with pytest.raises(MemoryError):
            pool.reserve(2, 1)
        pool.free(1)
        assert pool.free_blocks == 2
        pool.reserve(2, 1)

    def test_fork_shares_then_cow(self):
        pool = native.KVBlockPool(num_blocks=8, block_size=4)
        pool.reserve(1, 6)
        free_before = pool.free_blocks
        pool.fork(1, 2)                          # shares both blocks
        assert pool.free_blocks == free_before   # no new blocks
        np.testing.assert_array_equal(pool.block_table(1),
                                      pool.block_table(2))
        cp = pool.cow_last_block(2)              # shared → copy
        assert cp is not None
        src, dst = cp
        assert src == pool.block_table(1)[-1]
        assert dst == pool.block_table(2)[-1]
        assert src != dst
        # now exclusive: second CoW is a no-op
        assert pool.cow_last_block(2) is None
        # freeing the parent releases only its now-private last block ref
        pool.free(1)
        pool.free(2)
        assert pool.free_blocks == 8

    def test_fork_unknown_parent(self):
        pool = native.KVBlockPool(4, 4)
        with pytest.raises(KeyError):
            pool.fork(99, 1)

    def test_fork_reused_child_no_leak(self):
        """Re-forking onto a live child id releases its old blocks
        (regression: refcount leak on id reuse)."""
        pool = native.KVBlockPool(8, 4)
        pool.reserve(1, 8)           # 2 blocks
        for _ in range(10):          # would exhaust the pool if leaking
            pool.fork(1, 2)
        pool.free(1)
        pool.free(2)
        assert pool.free_blocks == 8
        # self-fork is a no-op
        pool.reserve(3, 4)
        assert pool.fork(3, 3) == 1
        pool.free(3)
        assert pool.free_blocks == 8


class TestTensorStore:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "weights.pits")
        rng = np.random.RandomState(1)
        tensors = {
            "w1": rng.randn(4, 8).astype(np.float32),
            "ids": np.arange(10, dtype=np.int64),
            "flag": np.array([True, False]),
            "scalar": np.float64(3.5) * np.ones((), np.float64),
        }
        native.save_tensors(path, tensors)
        back = native.load_tensors(path)
        assert set(back) == set(tensors)
        for k in tensors:
            np.testing.assert_array_equal(back[k], np.asarray(tensors[k]))
            assert back[k].dtype == np.asarray(tensors[k]).dtype

    def test_bfloat16(self, tmp_path):
        import ml_dtypes

        path = str(tmp_path / "bf16.pits")
        arr = np.arange(6, dtype=np.float32).astype(ml_dtypes.bfloat16)
        native.save_tensors(path, {"x": arr})
        back = native.load_tensors(path)
        assert back["x"].dtype == np.dtype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(back["x"], arr)

    def test_pit_save_load_pits_path(self, tmp_path):
        """pit.save/load route .pits files through the native store and the
        result round-trips a model state dict."""
        import paddle_infer_tpu as pit

        pit.seed(3)
        m = pit.nn.Linear(6, 3)
        path = str(tmp_path / "m.pits")
        pit.save(m.state_dict(), path)
        back = pit.load(path)
        m2 = pit.nn.Linear(6, 3)
        m2.set_state_dict(back)
        x = pit.to_tensor(np.ones((2, 6), np.float32))
        np.testing.assert_allclose(m2(x).numpy(), m(x).numpy(), rtol=1e-6)

    def test_missing_file(self):
        with pytest.raises(FileNotFoundError):
            native.load_tensors("/nonexistent/x.pits")

    def test_corrupt_file(self, tmp_path):
        # corruption must NOT look like a missing file (a resume path
        # treats FileNotFoundError as "no checkpoint yet")
        p = tmp_path / "junk.pits"
        p.write_bytes(b"NOTAPITSFILE" + b"\x00" * 64)
        with pytest.raises(ValueError):
            native.load_tensors(str(p))

    def test_corrupt_huge_ndim_fails_fast(self, tmp_path):
        # a truncated header claiming ndim ~2^31 must hit the corrupt
        # path immediately, not attempt a multi-GB allocation
        import struct

        p = tmp_path / "huge.pits"
        p.write_bytes(b"PITS" + struct.pack("<II", 1, 1)
                      + struct.pack("<I", 1) + b"x"        # name "x"
                      + struct.pack("<I", 0)               # dtype
                      + struct.pack("<I", 2**31 - 1))      # absurd ndim
        with pytest.raises(ValueError):
            native.load_tensors(str(p))

    def test_corrupt_huge_count_fails_fast(self, tmp_path):
        import struct

        p = tmp_path / "hugecount.pits"
        p.write_bytes(b"PITS" + struct.pack("<II", 1, 2**31 - 1)
                      + b"\x00" * 16)
        with pytest.raises(ValueError):
            native.load_tensors(str(p))
