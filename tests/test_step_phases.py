"""The serving step timed from inside: the scheduler iteration's phases
and per-step counts in the StepLog record (observability/stepclock.py,
serving/engine_core.py), the same intervals as ``engine.*`` spans in the
profiler's trace, the parts of the emit phase (the row loop, a request's
release with the prefix cache's insert and eviction walk inside it) and
the interpreter's collections as child spans and fields on the same
reads, the compiled step's memory analysis kept beside its cost
analysis, the Fleet step's compile event, and the server's step
histogram fed the synced step."""
import gc
import glob
import os

import numpy as np
import pytest

import paddle_infer_tpu as pit
from paddle_infer_tpu.inference.generation import (GenerationConfig,
                                                   PagedGenerationEngine)
from paddle_infer_tpu.models import GPTConfig, GPTForCausalLM
from paddle_infer_tpu.observability import StepLog, get_compile_log
from paddle_infer_tpu.observability.stepclock import ENGINE_PHASES, GcWatch
from paddle_infer_tpu.serving import EngineCore

PAGE = 8
PHASE_FIELDS = ("gap_s", "admit_s", "pack_s", "launch_s", "wait_s", "host_s")


@pytest.fixture(scope="module", autouse=True)
def _isolated_compile_log():
    """Every core here owns a fresh engine: the process-wide log must
    not carry their compiles (or another file's warm marks) across."""
    get_compile_log().reset()
    yield
    get_compile_log().reset()


@pytest.fixture(scope="module", autouse=True)
def _meshless():
    """These cores run unsharded: clear any hybrid mesh another module
    of the same worker left behind (``fleet.init`` in
    tests/test_zero_placement.py; ops and engines consult
    ``topology.get_current_mesh()`` at call time), which otherwise turns
    the one-device step into a sharded one with another step count and
    no memory analysis."""
    from paddle_infer_tpu.parallel import topology

    prev = topology.get_current_mesh()
    topology.set_current_mesh(None)
    yield
    topology.set_current_mesh(prev)


@pytest.fixture(scope="module")
def model():
    pit.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    m.eval()
    return m


@pytest.fixture
def make_core(model):
    cores = []

    def make(**kw):
        kw.setdefault("max_batch", 4)
        core = EngineCore(PagedGenerationEngine(model, page_size=PAGE), **kw)
        cores.append(core)
        return core

    yield make
    for c in cores:
        c.close()


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 96, (n,)).astype(np.int32)


def _drive(core, reqs, max_iters=400):
    for _ in range(max_iters):
        if all(r.done for r in reqs):
            return
        core.run_once()
    raise AssertionError("requests did not finish")


def _step_records(core):
    """The records of step-program launches: the ones the clock wrote."""
    return [r for r in core.steplog.records() if r["t_begin"] > 0.0]


def _serve_some(core):
    g = GenerationConfig(max_new_tokens=5)
    (a,) = core.submit(_prompt(1, 11), g)
    core.run_once()
    (b,) = core.submit(_prompt(2, 6), GenerationConfig(max_new_tokens=7))
    _drive(core, [a, b])
    return _step_records(core)


def test_phases_tile_the_run(make_core):
    core = make_core()
    recs = _serve_some(core)
    assert len(recs) >= 4
    assert recs[0]["gap_s"] == 0.0
    assert [r["step"] for r in recs] == list(range(1, len(recs) + 1))
    for r in recs:
        assert r["kernel"] == "ragged"
        assert all(r[f] >= 0.0 for f in PHASE_FIELDS)
        assert r["dispatch_s"] == pytest.approx(
            r["launch_s"] + r["wait_s"], abs=1e-9)
        assert r["wall_s"] == pytest.approx(
            r["dispatch_s"] + r["host_s"], abs=1e-9)
        assert r["launch_s"] > 0.0 and r["wait_s"] > 0.0
    for k, nxt in zip(recs, recs[1:]):
        end = k["t_begin"] + k["admit_s"] + k["pack_s"] + k["wall_s"]
        assert end + nxt["gap_s"] == pytest.approx(nxt["t_begin"], abs=1e-3)
    # the phases account for the whole run between the first and the last
    # step's beginning
    span = recs[-1]["t_begin"] - recs[0]["t_begin"]
    told = sum(sum(r[f] for f in PHASE_FIELDS) for r in recs[:-1]) \
        - recs[0]["gap_s"] + recs[-1]["gap_s"]
    assert told == pytest.approx(span, rel=0.01)
    # what is no launch of the step program keeps the defaults
    others = [r for r in core.steplog.records() if r["t_begin"] == 0.0]
    assert others and all(
        r["kind"] in ("evict", "page_copy", "prefill") for r in others)
    assert all(r["step"] == 0 and r["launch_s"] == 0.0 for r in others)


def test_failed_step_records_what_it_reached(make_core):
    from paddle_infer_tpu.serving.resilience.faultplane import (FaultPlane,
                                                                FaultSpec)

    core = make_core(fault_plane=FaultPlane([FaultSpec("decode.step",
                                                       at=2)]))
    (a,) = core.submit(_prompt(3, 9), GenerationConfig(max_new_tokens=4))
    core.run_once()
    core.run_once()                       # the fault fires before the launch
    failed = [r for r in _step_records(core) if r["failed"]]
    assert len(failed) == 1
    r = failed[0]
    assert r["wait_s"] == 0.0 and r["launch_s"] > 0.0
    assert r["dispatch_s"] == pytest.approx(r["launch_s"], abs=1e-9)
    assert r["wall_s"] == pytest.approx(r["dispatch_s"] + r["host_s"],
                                        abs=1e-9)
    assert r["pack_s"] > 0.0 and r["h2d_bytes"] == 0


def test_counts_equal_a_brute_force_count(make_core):
    """A batch mixing a chunked prompt, a warm-prefix suffix and decode
    rows: the record's counts against a count over the very arrays the
    step program was handed."""
    core = make_core(enable_prefix_cache=True, prefill_chunk=4,
                     token_budget=8)
    eng = core._engine
    seen = []
    real = eng.run_paged_program

    def spy(key, builder, *args):
        if key[0] == "serve-step":
            # the packer reuses its one buffer: keep this step's copy
            seen.append(tuple(np.array(a) for a in args))
        return real(key, builder, *args)

    eng.run_paged_program = spy
    shared = _prompt(7, 2 * PAGE)
    (first,) = core.submit(np.concatenate([shared, _prompt(8, 3)]),
                           GenerationConfig(max_new_tokens=3))
    _drive(core, [first])                 # its pages stay in the cache
    (dec,) = core.submit(_prompt(9, 5), GenerationConfig(max_new_tokens=12))
    for _ in range(3):
        core.run_once()                   # now a decode row
    (long,) = core.submit(_prompt(10, 19), GenerationConfig(max_new_tokens=3))
    (warm,) = core.submit(np.concatenate([shared, _prompt(11, 6)]),
                          GenerationConfig(max_new_tokens=3))
    _drive(core, [dec, long, warm])
    recs = _step_records(core)
    assert len(recs) == len(seen)
    mixes = set()
    for r, args in zip(recs, seen):
        (packed,) = args                  # one host array a step
        fields = core._step_in.views(packed)
        qlens, ctx = fields["qlens"], fields["ctx"]
        keys = resident = 0
        for q, c in zip(qlens.tolist(), ctx.tolist()):
            for i in range(q):
                keys += c + i + 1         # query i sees the cache + 0..i
            resident += (c + q) if q else 0
        assert r["attended_keys"] == keys
        assert r["resident_tokens"] == resident
        assert r["h2d_bytes"] == packed.nbytes == 4 * core._step_in.size
        assert (r["h2d_arrays"], r["d2h_arrays"]) == (1, 1)
        rows = [(q, c) for q, c in zip(qlens.tolist(), ctx.tolist()) if q]
        mixes.add((any(q == 1 for q, _ in rows),
                   any(q > 1 and c == 0 for q, c in rows),
                   any(q > 1 and c >= PAGE for q, c in rows)))
    # one launch carried a decode row, a cold chunk and a chunk that
    # started behind cached pages
    assert (True, True, True) in mixes
    assert warm.done and long.done


def test_program_temp_bytes_is_cached_beside_the_cost(make_core):
    import jax.monitoring

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _d, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    clog = get_compile_log()
    core = make_core()
    eng = core._engine
    (a,) = core.submit(_prompt(4, 9), GenerationConfig(max_new_tokens=4))
    _drive(core, [a])
    key = next(k for k in eng._program_costs if k[0] == "serve-step")
    mem = eng.program_memory(key)
    assert set(mem) == {"temp", "argument", "output"} and mem["temp"] > 0
    n_compiles, n_events = len(compiles), clog.count()
    (b,) = core.submit(_prompt(5, 9), GenerationConfig(max_new_tokens=6))
    _drive(core, [b])
    # warm: no backend compile (jit or AOT), no CompileLog event, and the
    # same cached analysis on every record
    assert len(compiles) == n_compiles and clog.count() == n_events
    assert eng.program_memory(key) is mem
    recs = _step_records(core)
    assert {r["program_temp_bytes"] for r in recs} == {mem["temp"]}
    assert eng.program_memory(("serve-step", "never-run")) is None


def _profile_events(trace_dir):
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "fleet.", "prefix.",
                                      "host.gc")):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def test_two_step_profile_holds_the_program_spans(make_core, tmp_path):
    import jax

    core = make_core()
    (a,) = core.submit(_prompt(6, 9), GenerationConfig(max_new_tokens=8))
    core.run_once()                                   # compiled, warm
    step = _tiny_train_step()
    x, y = _train_batch(8)
    step(x, y)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        core.run_once()
        core.run_once()
        step(x, y).numpy()
    finally:
        jax.profiler.stop_trace()
    events = _profile_events(str(tmp_path))
    steps = [e for e in events if e[0] == "engine.step"]
    recs = _step_records(core)[-2:]
    assert [e[3]["step_num"] for e in steps] == [r["step"] for r in recs]
    for _, s0, s1, _ in steps:
        inside = [e[0] for e in events
                  if e[0] != "engine.step" and e[0].startswith("engine.")
                  and s0 <= e[1] and e[2] <= s1]
        # the five phases in order, the read-back's first half inside the
        # wait and the row loop inside the emit
        assert inside == ["engine.admit", "engine.pack", "engine.launch",
                          "engine.wait", "engine.ready", "engine.emit",
                          "engine.emit_rows"]
    # the spans' lengths are the record's phases (one clock read each)
    for (_, s0, s1, _), r in zip(steps, recs):
        launch = next(e for e in events if e[0] == "engine.launch"
                      and s0 <= e[1] <= s1)
        assert (launch[2] - launch[1]) * 1e-9 == pytest.approx(
            r["launch_s"], abs=2e-3)
    train = [e for e in events if e[0] == "fleet.train_step"]
    assert len(train) == 1 and train[0][3]["step_num"] == 2


def _iterations(core):
    """(step record, the evict records of its iteration) for every step
    record: an evict record is written before the record of the step that
    released the row."""
    out, pending = [], []
    for r in core.steplog.records():
        if r["kind"] == "evict":
            pending.append(r)
        elif r["t_begin"] > 0.0:
            out.append((r, pending))
            pending = []
    return out


def test_children_tile_the_emit_phase(make_core):
    core = make_core(enable_prefix_cache=True)
    g = GenerationConfig(max_new_tokens=4)
    (a,) = core.submit(_prompt(21, 2 * PAGE + 3), g)
    (b,) = core.submit(_prompt(22, PAGE + 1),
                       GenerationConfig(max_new_tokens=7))
    _drive(core, [a, b])
    (c,) = core.submit(_prompt(23, 9), g)
    (d,) = core.submit(_prompt(24, 9), g)         # finish in one step
    _drive(core, [c, d])
    its = _iterations(core)
    assert sorted(len(ev) for _, ev in its)[-3:] == [1, 1, 2]
    for rec, evicts in its:
        assert rec["emit_rows_s"] > 0.0 and rec["release_s"] >= 0.0
        assert rec["emit_rows_s"] + rec["release_s"] <= rec["host_s"]
        assert 0.0 < rec["ready_s"] <= rec["wait_s"]
        # the thread's own CPU seconds lie inside the iteration's wall
        wall = (rec["admit_s"] + rec["pack_s"] + rec["launch_s"]
                + rec["wait_s"] + rec["host_s"])
        assert 0.0 < rec["cpu_s"] <= wall
        assert 0.0 <= rec["off_cpu_s"] <= wall - rec["wait_s"]
        assert rec["insert_s"] == sum(e["insert_s"] for e in evicts)
        assert rec["finished_rows"] == len(evicts)
        # one Span's seconds, written twice: no tolerance
        assert rec["release_s"] == sum(e["wall_s"] for e in evicts)
        for e in evicts:
            assert 0.0 < e["insert_s"] <= e["wall_s"]
            assert e["retained_blocks"] > 0 and e["finished_rows"] == 0
    finishes = [rec for rec, ev in its if len(ev) == 1]
    assert all(rec["release_s"] > 0.0 for rec in finishes)
    assert its[-1][1][-1]["retained_blocks"] == \
        core.prefix_cache.cached_blocks


def test_release_outside_an_iteration_is_timed_without_a_span(model):
    core = EngineCore(PagedGenerationEngine(model, page_size=PAGE),
                      max_batch=2)
    (a,) = core.submit(_prompt(25, 9), GenerationConfig(max_new_tokens=30))
    core.run_once()
    core.run_once()
    clock = core._clock
    assert clock.child("release").name is None        # the clock is closed
    core.close()                        # cancels the row: one evict record
    last = core.steplog.records()[-1]
    assert last["kind"] == "evict" and last["wall_s"] > 0.0
    assert clock.child_count("release") == 1          # close()'s
    fresh = EngineCore(PagedGenerationEngine(model, page_size=PAGE),
                       max_batch=2)
    try:
        assert fresh._clock is None and fresh._child("release").name is None
    finally:
        fresh.close()


def test_profile_nests_the_finish_spans(make_core, tmp_path):
    """A finish in a traced step: ``engine.release`` inside
    ``engine.emit_rows`` inside ``engine.emit``; the prefix cache's insert
    and its eviction walk inside the release; all on ``engine.step``'s
    clock."""
    import jax

    core = make_core(enable_prefix_cache=True, prefix_cache_watermark=0.02)
    (a,) = core.submit(_prompt(26, 3 * PAGE + 2),
                       GenerationConfig(max_new_tokens=3))
    core.run_once()                                   # compiled, warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        gc.collect()
        _drive(core, [a])
    finally:
        jax.profiler.stop_trace()
    events = _profile_events(str(tmp_path))

    def named(name):
        return [e for e in events if e[0] == name]

    def inside(child, parent):
        return parent[1] <= child[1] and child[2] <= parent[2]

    (release,) = named("engine.release")
    (insert,) = named("prefix.insert")
    (walk,) = named("prefix.evict")
    (rows,) = [e for e in named("engine.emit_rows") if inside(release, e)]
    (emit,) = [e for e in named("engine.emit") if inside(rows, e)]
    (step,) = [e for e in named("engine.step") if inside(emit, e)]
    assert inside(insert, release) and inside(walk, release)
    (wait,) = [e for e in named("engine.wait") if inside(e, step)]
    (ready,) = [e for e in named("engine.ready") if inside(e, step)]
    assert inside(ready, wait) and wait[2] <= emit[1]
    assert insert[2] <= walk[1]                       # insert, then evict
    assert named("host.gc")                 # the forced collection, spanned
    (rec, (evict,)) = [it for it in _iterations(core) if it[1]][-1]
    assert step[3]["step_num"] == rec["step"]
    assert (release[2] - release[1]) * 1e-9 == pytest.approx(
        evict["wall_s"], abs=2e-3)
    assert rec["evicted_blocks"] == evict["evicted_blocks"] > 0
    assert rec["evict_scanned_nodes"] == evict["evict_scanned_nodes"] > 0
    assert evict["evict_s"] > 0.0 and rec["evict_s"] == evict["evict_s"]


def test_a_collection_shows_in_the_step_it_ran_in(model):
    core = EngineCore(PagedGenerationEngine(model, page_size=PAGE),
                      max_batch=2)
    watch = core._gc
    try:
        assert watch._on_gc not in gc.callbacks        # not before the loop
        (a,) = core.submit(_prompt(27, 9),
                           GenerationConfig(max_new_tokens=12))
        core.run_once()
        assert gc.callbacks.count(watch._on_gc) == 1
        core.run_once()
        before = _step_records(core)[-1]
        real = core._engine.run_paged_program
        generation = [2]

        def collecting(key, builder, *args):
            gc.collect(generation[0])                  # inside an iteration
            return real(key, builder, *args)

        core._engine.run_paged_program = collecting
        core.run_once()
        rec = _step_records(core)[-1]
        assert rec["step"] == before["step"] + 1
        assert rec["gc_gen2"] == 1 and 0.0 < rec["gc_s"] < rec["launch_s"]
        generation[0] = 1                              # timed, not gen 2
        core.run_once()
        core._engine.run_paged_program = real
        nxt = _step_records(core)[-1]
        assert nxt["gc_gen2"] == 0 and 0.0 < nxt["gc_s"] < nxt["launch_s"]
        # generation 0 leaves the callback at its first comparison
        watch._on_gc("start", {"generation": 0})
        assert watch._open is None and watch._span is None
        assert gc.callbacks.count(watch._on_gc) == 1   # installed once
    finally:
        core.close()
    assert watch._on_gc not in gc.callbacks
    core.run_once()                                    # closed: stays off
    assert watch._on_gc not in gc.callbacks


def test_a_collection_is_booked_where_its_time_passed():
    """The watch's two callbacks driven by hand with made-up reads: steps
    tile 10.0 .. 10.5 .. 11.0 .. 11.5, and whenever the callbacks ran
    every second of a collection lands on the step it passed in, once."""
    watch = GcWatch()
    # another thread collects while the engine's thread sits in its wait,
    # and its stop callback has run by the time the record is written
    watch.began(10.2, 2)
    watch.ended(10.3)
    assert watch.book(10.0, 10.5) == (pytest.approx(0.1), 1)
    assert watch.book(10.5, 11.0) == (0.0, 0)
    # still open at the record (the other thread holds it, or handed the
    # GIL over before its stop callback): up to the record's end here, the
    # rest on the next record, counted as begun once
    watch.began(11.2, 2)
    assert watch.book(11.0, 11.5) == (pytest.approx(0.3), 1)
    watch.ended(11.6)
    assert watch.book(11.5, 12.0) == (pytest.approx(0.1), 0)
    assert watch.book(12.0, 12.5) == (0.0, 0)
    # it stopped between the record's read of `end` and its booking
    watch.began(12.7, 1)
    watch.ended(13.02)
    assert watch.book(12.5, 13.0) == (pytest.approx(0.3), 0)
    assert watch.book(13.0, 13.5) == (pytest.approx(0.02), 0)
    # it began after `end` was read: all of it is the next record's
    watch.began(14.01, 2)
    watch.ended(14.04)
    assert watch.book(13.5, 14.0) == (0.0, 0)
    assert watch.book(14.0, 14.5) == (pytest.approx(0.03), 1)
    # one across three records, two inside one record
    watch.began(14.9, 2)
    assert watch.book(14.5, 15.0) == (pytest.approx(0.1), 1)
    assert watch.book(15.0, 15.5) == (pytest.approx(0.5), 0)
    watch.ended(15.7)
    watch.began(15.8, 1)
    watch.ended(15.9)
    assert watch.book(15.5, 16.0) == (pytest.approx(0.3), 0)
    assert not watch._closed and not watch._carry and watch._open is None
    # a stop whose start the watch never saw (installed mid-collection)
    watch.ended(16.2)
    assert watch.book(16.0, 16.5) == (0.0, 0)


def test_another_threads_collection_lands_on_the_step_it_stalled(model):
    """What PR 40's chip run showed missing: a collection on another
    thread during a step's wait, whose stop callback runs after the
    step's record is written, is on THAT record up to its end, and only
    the rest on the next."""
    import time

    core = EngineCore(PagedGenerationEngine(model, page_size=PAGE),
                      max_batch=2)
    watch = core._gc
    gc.disable()            # no collection of the interpreter's own between
    try:
        (a,) = core.submit(_prompt(28, 9),
                           GenerationConfig(max_new_tokens=12))
        core.run_once()
        core.run_once()
        real = core._engine.run_paged_program
        began = []

        def collecting(key, builder, *args):
            began.append(time.monotonic())
            watch.began(began[0], 2)        # the other thread's start
            return real(key, builder, *args)

        core._engine.run_paged_program = collecting
        core.run_once()
        core._engine.run_paged_program = real
        rec = _step_records(core)[-1]
        end = rec["t_begin"] + sum(rec[f] for f in PHASE_FIELDS[1:])
        assert rec["gc_gen2"] == 1
        assert rec["gc_s"] == pytest.approx(end - began[0], abs=1e-6)
        assert rec["gc_s"] > rec["wait_s"]
        stopped = time.monotonic()
        watch.ended(stopped)                # its stop, a record late
        core.run_once()
        nxt = _step_records(core)[-1]
        assert nxt["gc_gen2"] == 0
        assert nxt["gc_s"] == pytest.approx(stopped - end, abs=1e-6)
        assert nxt["gc_s"] <= nxt["gap_s"]
        core.run_once()
        assert _step_records(core)[-1]["gc_s"] == 0.0
    finally:
        gc.enable()
        core.close()


def _tiny_train_step():
    from paddle_infer_tpu.parallel import (DistributedStrategy,
                                           FleetTrainStep, fleet)

    import jax

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy,
               devices=jax.devices()[:1])
    m = pit.nn.Linear(16, 4)
    opt = pit.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())

    def loss_fn(model, x, y):
        return pit.nn.functional.cross_entropy(model(x), y)

    return FleetTrainStep(m, loss_fn, opt, strategy=strategy)


def _train_batch(n):
    rng = np.random.RandomState(n)
    return (rng.randn(n, 16).astype(np.float32),
            rng.randint(0, 4, (n,)).astype(np.int64))


@pytest.fixture
def _fleet_reset():
    from paddle_infer_tpu.parallel import fleet, set_current_mesh

    yield
    set_current_mesh(None)
    fleet._state.initialized = False


def test_fleet_step_leaves_one_compile_event_per_signature(_fleet_reset):
    clog = get_compile_log()
    before = clog.count("fleet-train-step")
    step = _tiny_train_step()
    for n in (8, 8, 8, 4, 4, 8):
        step(*_train_batch(n))
    events = clog.events("fleet-train-step")[before:]
    assert clog.count("fleet-train-step") - before == 2
    assert len({e.key for e in events}) == 2
    assert all(e.wall_s > 0 for e in events)


def test_step_histogram_is_fed_the_synced_step(make_core):
    core = make_core()
    recs = _serve_some(core)
    snap = core.metrics.snapshot()
    hist = snap["histograms"]["step_wall"]
    assert hist["count"] == len(recs)
    mean_dispatch = sum(r["dispatch_s"] for r in recs) / len(recs)
    assert hist["sum"] / hist["count"] == pytest.approx(mean_dispatch,
                                                       rel=1e-6)
    # not the launch alone, which returns before the device is done
    assert hist["sum"] > sum(r["launch_s"] for r in recs)
    itl = snap["histograms"]["itl"]
    decode = [r for r in recs if r["kind"] in ("decode", "mixed")
              and r["emitted_tokens"]]
    assert itl["count"] == len(decode) > 0
    assert itl["sum"] <= sum(r["dispatch_s"] for r in decode) + 1e-9


def test_step_trace_is_bounded_like_the_steplog(model):
    core = EngineCore(PagedGenerationEngine(model, page_size=PAGE),
                      max_batch=2, steplog=StepLog(capacity=4))
    try:
        (a,) = core.submit(_prompt(12, 6),
                           GenerationConfig(max_new_tokens=9))
        _drive(core, [a])
        assert core.step_trace.maxlen == 4 == len(core.step_trace)
        assert core.step_trace[-1]["step"] == core._step_idx
    finally:
        core.close()
