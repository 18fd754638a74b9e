"""Parts of a host phase (ISSUE 51): ``RunObserver.part`` times what a
phase does inside the phase, the persistent cache's read-back is timed
where JAX reads, and `init` and a snapshot say their parts on every
BFS engine.  Stub engines and fake clocks only: no level program of a
real module is built here."""

import contextlib
import json
import os
import time

import numpy as np
import pytest

from tests.test_obs import _Recorder
from tpuvsr.engine.paged_bfs import PagedBFS
from tpuvsr.obs import (Metrics, RunObserver, builds, read_journal, spans,
                        validate_metrics)
from tpuvsr.testing import (SYMPAIR_DISTINCT, SYMPAIR_ORBITS,
                            stub_device_engine, stub_sharded_engine,
                            stub_sym_engine, stub_sym_sharded)

INIT_PARTS = (spans.INIT_STATES, spans.INIT_FINGERPRINT, spans.INIT_DEVICE)
SNAPSHOT_PARTS = (spans.CHECKPOINT_PULL, spans.CHECKPOINT_WRITE,
                  spans.CHECKPOINT_DURABLE)


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, secs):
        self.t += secs


class _Res:
    ok = True
    elapsed = 0.0


def _observer(clock=None, **kw):
    obs = RunObserver(**kw)
    if clock is not None:
        obs.metrics = Metrics(clock=clock)
    obs.engine = "device"
    obs.start(time.time(), backend="host")
    return obs


# ---------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------
@pytest.mark.parametrize("name", ["tpuvsr.engine.init.zeroing",
                                  spans.INIT, "init"]
                         + list(spans.READ_BACK_PARTS))
def test_part_outside_the_vocabulary_raises(name):
    obs = _observer(annotation=lambda: None)
    with obs.span(spans.INIT), pytest.raises(KeyError):
        obs.part(name)
    obs.close()


def test_the_vocabulary_names_three_parts_of_three_phases():
    by_phase = {}
    for name, (phase, part) in spans.ENGINE_PARTS.items():
        span = next(s for s, p in spans.ENGINE_SPANS.items() if p == phase)
        assert name == f"{span}.{part}"
        by_phase.setdefault(phase, []).append(part)
    assert {k: len(v) for k, v in by_phase.items()} == {
        "compile": 3, "init": 3, "checkpoint": 3}
    assert set(spans.READ_BACK_PARTS) == {
        n for n, (phase, _) in spans.ENGINE_PARTS.items()
        if phase == "compile"}


def _scripted(clock, with_parts):
    """One init phase with a build inside its second stretch and one
    snapshot, on a fake clock; parts around the stretches or not."""
    obs = _observer(clock, annotation=lambda: None)

    def stretch(name, secs, inner=0.0):
        with obs.part(name) if with_parts else contextlib.nullcontext():
            clock.tick(secs)
            if inner:
                with obs.span(spans.BUILD):
                    clock.tick(inner)
    clock.tick(0.5)
    with obs.span(spans.INIT):
        stretch(spans.INIT_STATES, 1.0)
        stretch(spans.INIT_DEVICE, 2.0, inner=4.0)
        clock.tick(0.25)                # under no part
        stretch(spans.INIT_STATES, 0.5)
    with obs.span(spans.CHECKPOINT, depth=1):
        stretch(spans.CHECKPOINT_PULL, 0.125)
        stretch(spans.CHECKPOINT_DURABLE, 0.375)
    return obs.finish(_Res()).metrics


def test_a_phase_reads_the_same_with_and_without_parts():
    plain = _scripted(_Clock(), with_parts=False)
    parted = _scripted(_Clock(), with_parts=True)
    assert parted["phases"] == plain["phases"]
    assert plain["phases"]["init"] == 3.75
    assert plain["phases"]["compile"] == 4.0
    assert "phase_parts" not in plain
    # a part's seconds leave out what an inner span took from the
    # phase, and a part opened twice adds up
    assert parted["phase_parts"] == {
        "init": {"states": 1.5, "device": 2.0},
        "checkpoint": {"pull": 0.125, "durable": 0.375}}


def test_parts_sum_to_at_most_their_phase():
    doc = _scripted(_Clock(), with_parts=True)
    for phase, parts in doc["phase_parts"].items():
        assert sum(parts.values()) <= doc["phases"][phase]
    assert sum(doc["phase_parts"]["init"].values()) == 3.5      # of 3.75


@pytest.mark.parametrize("name", INIT_PARTS + SNAPSHOT_PARTS)
def test_a_part_opened_outside_its_phase_raises(name):
    obs = _observer(annotation=lambda: None)
    with pytest.raises(RuntimeError):       # under the root frame
        with obs.part(name):
            pass
    phase = spans.ENGINE_PARTS[name][0]
    other = spans.CHECKPOINT if phase == "init" else spans.INIT
    with obs.span(other), pytest.raises(RuntimeError):
        with obs.part(name):
            pass
    # and under an inner span of its own phase: the innermost counts
    span = spans.INIT if phase == "init" else spans.CHECKPOINT
    with obs.span(span), obs.span(spans.HOST_SYNC), \
            pytest.raises(RuntimeError):
        with obs.part(name):
            pass
    doc = obs.finish(_Res()).metrics
    assert "phase_parts" not in doc


def test_a_part_makes_no_annotation_with_profiling_off():
    obs = _observer(annotation=lambda: None)
    with obs.span(spans.INIT):
        part = obs.part(spans.INIT_STATES, rows=3)
        assert part._annotation is None
        with part:
            pass
    assert obs.finish(_Res()).metrics["phase_parts"]["init"]["states"] >= 0


def test_a_part_is_an_annotation_of_its_name_inside_its_phase():
    rec = _Recorder()
    obs = _observer(annotation=lambda: rec)
    with obs.span(spans.INIT):
        with obs.part(spans.INIT_FINGERPRINT):
            pass
    obs.finish(_Res())
    inner = rec.log[1:-1]       # inside the root's open and close
    assert inner == [("open", spans.INIT, {}),
                     ("open", spans.INIT_FINGERPRINT, {}),
                     ("close", spans.INIT_FINGERPRINT),
                     ("close", spans.INIT)]


def test_a_part_cut_by_an_abnormal_exit_charges_nothing():
    obs = _observer(annotation=lambda: None)
    with obs.span(spans.INIT):
        part = obs.part(spans.INIT_DEVICE)
        part.__enter__()
        obs.close()             # drains the frames under the part
        part.__exit__(None, None, None)
    assert obs.metrics.parts == {}


# ---------------------------------------------------------------------
# the metrics document
# ---------------------------------------------------------------------
def _document(**extra):
    doc = {"schema": "tpuvsr-metrics/1", "run_id": "r", "engine": "device",
           "elapsed_s": 1.0, "phases": {"init": 0.5}, "counters": {},
           "gauges": {}, "levels": []}
    doc.update(extra)
    return doc


def test_a_document_without_the_section_validates():
    assert "phase_parts" not in validate_metrics(_document())
    validate_metrics(_document(phase_parts={}))
    validate_metrics(_document(phase_parts={"init": {"states": 0.25}}),
                     strict=True)


@pytest.mark.parametrize("section", [[], {"init": 0.5},
                                     {"init": {"states": -1.0}},
                                     {"init": {"states": "fast"}}])
def test_an_ill_formed_section_does_not_validate(section):
    with pytest.raises(ValueError):
        validate_metrics(_document(phase_parts=section))


# ---------------------------------------------------------------------
# the read-back, where it happens
# ---------------------------------------------------------------------
def test_this_jax_has_the_read_back_seam():
    assert builds.install_read_back_seam() is True
    from jax._src import compilation_cache as cc
    assert cc.decompress_executable.__module__ == builds.__name__


def test_build_meter_splits_a_read_back_by_its_events():
    reported = []
    rec = _Recorder()
    m = builds.BuildMeter(report=reported.append, clock=lambda: 100.0)
    m.annotation = rec
    m.duration(builds.LOWER_EVENT, 1.0, "jit_level")
    m.read_back(spans.BUILD_CACHE_READ, 0.25, 1000)
    m.read_back(spans.BUILD_CACHE_DECOMPRESS, 0.5, 4000)
    assert rec.log == [("open", spans.BUILD_EXECUTABLE_LOAD, {})]
    m.event(builds.CACHE_HIT_EVENT)
    m.duration(builds.CACHE_LOAD_EVENT, 2.0)
    assert rec.log[-1] == ("close", spans.BUILD_EXECUTABLE_LOAD)
    m.duration(builds.BACKEND_EVENT, 2.25, "jit_level")
    first = {"cache_read_s": 0.25, "cache_decompress_s": 0.5,
             "executable_load_s": 1.25, "cache_read_bytes": 1000,
             "cache_decompressed_bytes": 4000}
    assert m.read_back_totals == first and m.cache_load_s == 2.0
    assert {k: reported[0][k] for k in builds.READ_BACK_KEYS} == first
    # a program that was compiled says nothing of a read-back, and a
    # load that raised (no retrieval event) leaves no annotation open
    m.duration(builds.LOWER_EVENT, 1.0, "jit_other")
    m.read_back(spans.BUILD_CACHE_READ, 0.125, 10)
    m.read_back(spans.BUILD_CACHE_DECOMPRESS, 0.125, 40)
    m.event(builds.CACHE_MISS_EVENT)
    m.duration(builds.BACKEND_EVENT, 3.0, "jit_other")
    assert rec.log[-1] == ("close", spans.BUILD_EXECUTABLE_LOAD)
    assert m.read_back_totals == first
    assert not set(builds.READ_BACK_KEYS) & set(reported[1])


def test_without_the_seam_the_gauges_are_absent(monkeypatch):
    m = builds.BuildMeter()
    doc = Metrics()
    monkeypatch.setattr(builds, "_seam", False)
    m.stamp(doc)
    assert "build_cache_load_s" in doc.gauges
    assert not [k for k in doc.gauges if k in (
        "build_cache_read_s", "build_cache_decompress_s",
        "build_executable_load_s")]
    assert "build_cache_read_bytes" not in doc.counters
    monkeypatch.setattr(builds, "_seam", True)
    m.stamp(doc)
    assert doc.gauges["build_executable_load_s"] == 0.0
    assert doc.counters["build_cache_decompressed_bytes"] == 0


@pytest.fixture
def scratch_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own, every
    program kept, and the session's cache back afterwards."""
    import jax
    from jax._src import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    cc.reset_cache()
    try:
        yield str(tmp_path / "cache")
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_the_delegating_cache_returns_what_was_put(scratch_cache):
    import jax
    from jax._src import compilation_cache as cc
    cache = cc._get_cache(jax.devices()[0].client)
    assert type(cache).__name__ == "TimedCache"
    assert str(cache._path) == scratch_cache
    entry = bytes(range(256)) * 64
    cache.put("some-key", entry)
    assert cache.get("some-key") == entry
    assert cache.get("another-key") is None
    # same directory, same keys: JAX's own cache reads the same bytes
    assert cache._inner.get("some-key") == entry
    assert any(name.startswith("some-key")
               for name in os.listdir(scratch_cache))


def test_a_read_back_splits_into_three_that_sum_to_the_load(scratch_cache):
    import jax
    import jax.numpy as jnp

    def persisted_for_phase_parts(x):
        return jnp.cos(x) * 3.0 + jnp.flip(x)

    def job():
        obs = RunObserver(annotation=lambda: None)
        obs.engine = "device"
        obs.start(time.time(), backend="cpu")
        out = jax.jit(persisted_for_phase_parts)(jnp.arange(32.0))
        out.block_until_ready()
        return obs.finish(_Res()).metrics

    first = job()
    jax.clear_caches()
    second = job()
    c1, c2 = first["counters"], second["counters"]
    # put, then get: the wrapper adds no miss and loses no hit
    assert c1["build_cache_misses"] >= 1 and c1["build_cache_hits"] == 0
    assert c2["build_cache_misses"] == 0
    assert c2["build_cache_hits"] == c1["build_cache_misses"]
    g1, g2 = first["gauges"], second["gauges"]
    assert g1["build_cache_load_s"] == 0.0
    assert g1["build_cache_read_s"] == g1["build_executable_load_s"] == 0.0
    assert c1["build_cache_read_bytes"] == 0
    assert g2["build_cache_load_s"] > 0
    assert g2["build_cache_read_s"] > 0 and g2["build_cache_decompress_s"] > 0
    assert abs(g2["build_cache_read_s"] + g2["build_cache_decompress_s"]
               + g2["build_executable_load_s"]
               - g2["build_cache_load_s"]) <= 1e-6 * c2["build_cache_hits"] \
        + 1e-6
    stored = sum(os.path.getsize(os.path.join(scratch_cache, n))
                 for n in os.listdir(scratch_cache) if n.endswith("-cache"))
    assert c2["build_cache_read_bytes"] == stored
    assert c2["build_cache_decompressed_bytes"] > 0


# ---------------------------------------------------------------------
# init and a snapshot, on the three engines
# ---------------------------------------------------------------------
ENGINES = {
    "device": stub_device_engine,
    # canon on: the fingerprint part on the path SH and RS take
    "sym": stub_sym_engine,
    "sharded": lambda: stub_sharded_engine(n_devices=2),
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One run from Init of each stub engine, a snapshot a level."""
    done = {}

    def run(kind):
        if kind not in done:
            tmp = tmp_path_factory.mktemp(kind)
            rec = _Recorder()
            jp = str(tmp / "j.jsonl")
            res = ENGINES[kind]().run(
                checkpoint_path=str(tmp / "ck"),
                obs=RunObserver(journal_path=jp, annotation=lambda: rec))
            assert res.ok
            done[kind] = res.metrics, rec, read_journal(jp)
        return done[kind]
    return run


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_run_from_init_reports_its_three_parts(runs, kind):
    doc, rec, _ = runs(kind)
    validate_metrics(doc)
    parts = doc["phase_parts"]["init"]
    assert sorted(parts) == ["device", "fingerprint", "states"]
    assert sum(parts.values()) <= doc["phases"]["init"] + 1e-6
    # what `init` does outside a part is bookkeeping
    assert sum(parts.values()) >= 0.9 * doc["phases"]["init"]
    for name in INIT_PARTS:
        assert rec.opened().count(name) >= 1
    assert rec.opened().count(spans.INIT_FINGERPRINT) == 1


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_runs_snapshots_report_their_three_parts(runs, kind):
    doc, rec, journal = runs(kind)
    parts = doc["phase_parts"]["checkpoint"]
    assert sorted(parts) == ["durable", "pull", "write"]
    assert sum(parts.values()) <= doc["phases"]["checkpoint"] + 1e-6
    events = [e for e in journal if e["event"] == "checkpoint"]
    assert len(events) == doc["counters"]["checkpoints"] >= 1
    for name in SNAPSHOT_PARTS[1:]:
        assert rec.opened().count(name) == len(events)
    # each event says what its own snapshot added; together, the phase's
    for part, secs in parts.items():
        assert abs(sum(e["parts"][part] for e in events) - secs) < 1e-4


def _snapshot_arguments():
    slots = np.zeros((64, 5), np.uint32)
    slots[[3, 9], :] = 7
    return dict(
        slots=slots, frontier={"x": np.arange(6, dtype=np.int32)},
        n_front=4, h_parent=np.full(4, -1, np.int64),
        h_action=np.zeros(4, np.int32), h_param=np.zeros(4, np.int32),
        init_dense=[{"x": np.int32(0)}], level_sizes=[1, 3], depth=1,
        fp_count=2, states_generated=5, max_msgs=4, expand_mults=[1],
        elapsed=0.5)


def test_save_checkpoint_reports_its_three_parts_once_each(tmp_path):
    from tpuvsr.engine.checkpoint import (FORMAT_VERSION, load_checkpoint,
                                          save_checkpoint)
    rec = _Recorder()
    jp = str(tmp_path / "j.jsonl")
    obs = _observer(journal_path=jp, annotation=lambda: rec)
    path = str(tmp_path / "snap")
    with obs.span(spans.CHECKPOINT, depth=1):
        staged = save_checkpoint(path, obs=obs, **_snapshot_arguments())
    obs.checkpoint(path, 1, 2, staged, FORMAT_VERSION)
    doc = obs.finish(_Res()).metrics
    assert [rec.opened().count(name) for name in SNAPSHOT_PARTS] == [1, 1, 1]
    parts = doc["phase_parts"]["checkpoint"]
    assert sorted(parts) == ["durable", "pull", "write"]
    assert 0 < sum(parts.values()) <= doc["phases"]["checkpoint"]
    event, = [e for e in read_journal(jp) if e["event"] == "checkpoint"]
    assert event["parts"] == parts and event["bytes"] == staged
    assert load_checkpoint(path)["fp_count"] == 2


def test_save_checkpoint_without_an_observer_opens_no_part(tmp_path):
    from tpuvsr.engine.checkpoint import save_checkpoint
    path = str(tmp_path / "snap")
    assert save_checkpoint(path, **_snapshot_arguments()) > 0
    with open(os.path.join(path, "manifest.json")) as f:
        assert json.load(f)["depth"] == 1


# ---------------------------------------------------------------------
# the canonical fingerprint of a batch: one compiled program a model
# (ISSUE 54)
# ---------------------------------------------------------------------
def _sym_batches(eng):
    """The Init batch of the SymPair fixture as `_register_init`
    stacks it, and every reachable state: a and b over 0..3."""
    dense = [eng.codec.encode(st) for st in eng.spec.init_states()]
    init = {k: np.stack([d[k] for d in dense]) for k in dense[0]}
    a, b = (x.reshape(-1).astype(np.int32)
            for x in np.meshgrid(np.arange(4), np.arange(4)))
    zeros = np.zeros_like(a)
    return {"init": init,
            "reachable": {"status": zeros, "a": a, "b": b, "err": zeros}}


@pytest.fixture(scope="module")
def sym_engine():
    return stub_sym_engine()


@pytest.mark.parametrize("which", ["init", "reachable"])
def test_canon_fp_batch_is_the_eager_fingerprint_bit_for_bit(sym_engine,
                                                             which):
    import jax
    import jax.numpy as jnp
    model = sym_engine.model
    assert model.canon is not None
    batch = _sym_batches(sym_engine)[which]
    eager = jax.vmap(model.canon.fingerprint_fn(model.kern))(
        {k: jnp.asarray(v) for k, v in batch.items()})
    got = np.asarray(model.fp_batch(batch))
    assert got.dtype == np.uint32 == np.asarray(eager).dtype
    assert got.shape == (len(batch["a"]), 4)
    np.testing.assert_array_equal(got, np.asarray(eager))
    # orbit-mates share a fingerprint, so the batch holds its orbits
    want = {"init": 1, "reachable": SYMPAIR_ORBITS}[which]
    assert len({tuple(row) for row in got}) == want


@pytest.mark.parametrize("which", ["init", "reachable"])
def test_canon_fp_batch_traces_once_a_batch_shape(monkeypatch, which):
    eng = stub_sym_engine()
    kern, traces = eng.model.kern, []
    fingerprint = kern.fingerprint
    # the kernel's Python body runs while a program is traced only
    monkeypatch.setattr(
        kern, "fingerprint",
        lambda st: traces.append(1) or fingerprint(st), raising=False)
    batch = _sym_batches(eng)[which]
    first = np.asarray(eng.model.fp_batch(batch))
    assert len(traces) == 1
    again = {k: v.copy() for k, v in batch.items()}
    np.testing.assert_array_equal(np.asarray(eng.model.fp_batch(again)),
                                  first)
    assert len(traces) == 1


def test_canon_off_keeps_the_kernels_own_fingerprint_batch():
    eng = stub_sym_engine(symmetry=False)
    assert eng.model.canon is None and eng.model._canon_fp is None
    batch = _sym_batches(eng)["reachable"]
    fps = np.asarray(eng.model.fp_batch(batch))
    np.testing.assert_array_equal(
        fps, np.asarray(eng.model.kern.fingerprint_batch(batch)))
    assert len({tuple(row) for row in fps}) == SYMPAIR_DISTINCT


SYM_ENGINES = {
    "device": stub_sym_engine,
    "paged": lambda: stub_sym_engine(cls=PagedBFS),
    "sharded": stub_sym_sharded,
}


@pytest.mark.parametrize("kind", sorted(SYM_ENGINES))
def test_a_second_run_with_canon_on_builds_nothing(kind):
    """What a bfs cell does: a warm-up and a window on one engine
    object.  The window finds the canonical fingerprint built."""
    eng = SYM_ENGINES[kind]()
    warm = eng.run(obs=RunObserver())
    res = eng.run(obs=RunObserver())
    assert warm.ok and res.ok
    assert res.distinct_states == warm.distinct_states == SYMPAIR_ORBITS
    assert warm.metrics["counters"]["build_programs"] > 0
    counters = res.metrics["counters"]
    assert counters["build_programs"] == 0
    assert counters.get("grows", 0) == 0
    assert res.metrics["gauges"]["build_trace_s"] == 0.0
    assert res.metrics["phase_parts"]["init"]["fingerprint"] >= 0.0
