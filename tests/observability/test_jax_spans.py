"""The device executor's phases as spans of the task-span pipeline.

``JaxExecutor`` opens a ``task_scope`` around each segment, eager op and
flush and times its phases with ``scope_span``; the spans ride its
``TaskEndEvent``s to a ``TraceCollector`` and are totalled by name in
``executor_stats``. Unarmed, none of it may cost a span or a device sync."""

from __future__ import annotations

import time

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
from cubed_tpu.observability import accounting
from cubed_tpu.observability.analytics import (
    _attach_spans,
    _interior_buckets,
    analyze,
)
from cubed_tpu.observability.callback import _ComputeAggregator
from cubed_tpu.observability.collect import TraceCollector
from cubed_tpu.runtime.executors import jax as jxm
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.runtime.executors.python import PythonDagExecutor
from cubed_tpu.storage.store import ZarrV2Array

SIDE, CHUNK = 400, 200
NBYTES = SIDE * SIDE * 8

#: every span of the table that a Zarr-to-Zarr add exercises once the
#: program is compiled (``jax.trace_lower`` / ``jax.compile``: see the
#: first-compute test)
ZARR_ADD_SPANS = {
    "jax.preload", "jax.h2d", "storage_read", "jax.struct_key",
    "jax.dispatch", "jax.flush", "jax.device_wait", "jax.d2h",
    "jax.write_wait", "chunk_encode", "storage_write", "fsync",
}

#: counted armed or not, each a whole number, 0 and not absent: what the two
#: streaming pipelines waited for and how many pages a preload made resident
PIPELINE_COUNTERS = ("write_wait_us", "stage_wait_us", "preload_page_faults")


class _Capture:
    """Keeps a compute's ``executor_stats`` and its task events."""

    def __init__(self):
        self.stats = None
        self.events = []

    def on_task_end(self, event):
        self.events.append(event)

    def on_compute_end(self, event):
        self.stats = event.executor_stats


@pytest.fixture(autouse=True)
def _no_operator_override(monkeypatch):
    monkeypatch.delenv(accounting.SPANS_ENV_VAR, raising=False)


@pytest.fixture
def sources(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB")
    rng = np.random.default_rng(7)
    paths = []
    for name in "ab":
        path = str(tmp_path / f"{name}.zarr")
        ct.to_zarr(
            ct.from_array(rng.random((SIDE, SIDE)), chunks=(CHUNK, CHUNK), spec=spec),
            path,
        )
        paths.append(path)
    return spec, paths


def _add(sources):
    spec, (pa, pb) = sources
    return xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec))


def _store(sources, tmp_path, name, callbacks, executor=None):
    """One Zarr-to-Zarr add on fresh arrays; returns its capture."""
    cap = _Capture()
    ct.to_zarr(
        _add(sources), str(tmp_path / f"{name}.zarr"),
        executor=executor or JaxExecutor(), callbacks=[cap, *callbacks],
    )
    return cap


def _spans(collector) -> list:
    return [s for rec in collector._records for s in rec["spans"]]


def _attrs(span, *but) -> dict:
    """A span's attributes but the named ones (a count of pages, a wait:
    what no test can know beforehand)."""
    return {k: v for k, v in span["attrs"].items() if k not in but}


def test_zarr_add_yields_every_span_each_inside_its_parent(sources, tmp_path):
    tc = TraceCollector(trace_dir=None)
    _store(sources, tmp_path, "c", [tc])
    assert ZARR_ADD_SPANS <= {s["name"] for s in _spans(tc)}
    parents_of = {}
    for rec in tc._records:
        by_id = {s["id"]: s for s in rec["spans"]}
        assert len(by_id) == len(rec["spans"]), "span ids repeat in a task"
        for s in rec["spans"]:
            if "parent" not in s:
                continue
            p = by_id[s["parent"]]
            parents_of.setdefault(s["name"], set()).add(p["name"])
            # time.time() has 0.24 us of resolution at today's epoch
            assert p["ts"] <= s["ts"] + 1e-6
            assert s["ts"] + s["dur"] <= p["ts"] + p["dur"] + 1e-6
        # every span lies inside the task record that carries it
        for s in rec["spans"]:
            assert rec["start"] - 1e-3 <= s["ts"]
            assert s["ts"] + s["dur"] <= rec["end"] + 1e-3
    assert parents_of["jax.h2d"] == {"jax.preload"}
    assert parents_of["storage_read"] == {"jax.preload"}  # no mesh: read, then put
    assert parents_of["jax.device_wait"] == {"jax.flush"}
    assert parents_of["jax.d2h"] == {"jax.flush"}
    assert parents_of["jax.write_wait"] == {"jax.flush"}
    assert parents_of["chunk_encode"] == {"jax.flush"}
    assert parents_of["storage_write"] == {"jax.flush"}
    assert "storage_write" in parents_of["fsync"]
    by_name = {s["name"]: s for s in _spans(tc)}
    assert _attrs(by_name["jax.preload"], "faults") == {
        "bytes": NBYTES, "chunks": 4, "streamed": True,
    }
    assert by_name["jax.h2d"]["attrs"]["bytes"] == CHUNK * CHUNK * 8  # one a chunk
    assert by_name["jax.flush"]["attrs"] == {"bytes": NBYTES, "chunks": 4}
    assert type(by_name["jax.preload"]["attrs"]["faults"]) is int
    assert by_name["jax.preload"]["attrs"]["faults"] >= 0
    assert type(by_name["jax.h2d"]["attrs"]["wait_us"]) is int
    assert by_name["jax.d2h"]["attrs"]["bytes"] == CHUNK * CHUNK * 8


@pytest.mark.parametrize("mode", ["write", "verify"])
def test_a_streamed_preload_is_one_span_a_source_with_a_read_and_a_put_a_chunk(
    sources, tmp_path, mode
):
    """The contract of the streamed preload: one ``jax.preload`` a source,
    marked ``streamed`` with its ``chunks``; directly inside it one
    ``storage_read`` and then one ``jax.h2d`` a chunk (the read is not inside
    the put, as it is under a mesh); in mode ``verify`` an
    ``integrity_verify`` after every read."""
    spec, paths = sources
    spec = ct.Spec(work_dir=spec.work_dir, allowed_mem="500MB", integrity=mode)
    tc = TraceCollector(trace_dir=None)
    stats = _store((spec, paths), tmp_path, "c", [tc]).stats
    (rec,) = [r for r in tc._records if any(s["name"] == "jax.preload" for s in r["spans"])]
    preloads = [s for s in rec["spans"] if s["name"] == "jax.preload"]
    assert len(preloads) == 2
    per_chunk = ["storage_read", "integrity_verify", "jax.h2d"]
    if mode == "write":
        per_chunk.remove("integrity_verify")
    for preload in preloads:
        assert _attrs(preload, "faults") == {"bytes": NBYTES, "chunks": 4, "streamed": True}
        inside = sorted(
            (s for s in rec["spans"] if s.get("parent") == preload["id"]),
            key=lambda s: s["id"],
        )
        assert [s["name"] for s in inside] == per_chunk * 4
        reads = [s for s in inside if s["name"] == "storage_read"]
        assert sorted(s["attrs"]["key"] for s in reads) == ["0.0", "0.1", "1.0", "1.1"]
        for s in inside:
            if s["name"] != "integrity_verify":
                assert s["attrs"]["bytes"] == CHUNK * CHUNK * 8
    assert stats["span_n"]["jax.preload"] == 2
    assert stats["span_n"]["jax.h2d"] == stats["span_n"]["storage_read"] == 8
    assert stats["span_n"].get("integrity_verify", 0) == (8 if mode == "verify" else 0)
    assert stats["h2d_stream_bytes"] == stats["h2d_bytes"] == 2 * NBYTES
    assert stats.get("chunks_verified", 0) == (8 if mode == "verify" else 0)


def test_a_source_of_one_chunk_is_a_preload_that_did_not_stream(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB")
    path = str(tmp_path / "one.zarr")
    ct.to_zarr(ct.from_array(np.ones((CHUNK, CHUNK)), chunks=(CHUNK, CHUNK), spec=spec), path)
    tc, cap = TraceCollector(trace_dir=None), _Capture()
    ct.to_zarr(
        xp.negative(ct.from_zarr(path, spec=spec)), str(tmp_path / "out.zarr"),
        executor=JaxExecutor(), callbacks=[tc, cap],
    )
    (preload,) = [s for s in _spans(tc) if s["name"] == "jax.preload"]
    assert _attrs(preload, "faults") == {
        "bytes": CHUNK * CHUNK * 8, "chunks": 1, "streamed": False,
    }
    (h2d,) = [s for s in _spans(tc) if s["name"] == "jax.h2d"]
    # put whole, on the one device: no buffer waited for
    assert h2d["attrs"] == {"bytes": CHUNK * CHUNK * 8, "device": 0}
    assert cap.stats["h2d_stream_bytes"] == 0 < cap.stats["h2d_bytes"]


def test_first_compute_traces_and_compiles_the_second_is_a_struct_hit(
    sources, tmp_path
):
    jxm._STRUCT_CACHE.clear()
    jxm._SEGMENT_CACHE.clear()
    seen = []
    for name in ("first", "second"):
        tc = TraceCollector(trace_dir=None)
        _store(sources, tmp_path, name, [tc])
        seen.append({s["name"]: s for s in _spans(tc)})
    first, second = seen
    assert {"jax.trace_lower", "jax.compile"} <= set(first)
    assert first["jax.dispatch"]["attrs"]["struct_hit"] is False
    assert not {"jax.trace_lower", "jax.compile"} & set(second)
    assert second["jax.dispatch"]["attrs"]["struct_hit"] is True


def test_executor_stats_span_totals_are_the_collectors_spans(sources, tmp_path):
    tc = TraceCollector(trace_dir=None)
    stats = _store(sources, tmp_path, "c", [tc]).stats
    spans = _spans(tc)
    names = {s["name"] for s in spans}
    assert set(stats["span_s"]) == set(stats["span_n"]) == names
    for name in names:
        mine = [s for s in spans if s["name"] == name]
        assert stats["span_n"][name] == len(mine)
        assert stats["span_s"][name] == pytest.approx(sum(s["dur"] for s in mine))
        assert stats["span_self_s"][name] <= stats["span_s"][name] + 1e-12
    assert stats["spans_dropped"] == 0
    # self time is duration less the spans directly inside on the same
    # thread: the flush's writer works beside it
    (flush,) = [s for s in spans if s["name"] == "jax.flush"]
    (rec,) = [r for r in tc._records if flush in r["spans"]]
    inside = [s for s in rec["spans"] if s.get("parent") == flush["id"]]
    own = [s for s in inside if "thread" not in s.get("attrs", {})]
    assert {s["name"] for s in inside} - {s["name"] for s in own} == {
        "chunk_encode", "storage_write",
    }
    assert stats["span_self_s"]["jax.flush"] == pytest.approx(
        flush["dur"] - sum(s["dur"] for s in own)
    )
    assert stats["span_self_s"]["fsync"] == pytest.approx(stats["span_s"]["fsync"])
    # the writer's own nesting is as it was: a write less its fsyncs
    assert stats["span_self_s"]["storage_write"] == pytest.approx(
        stats["span_s"]["storage_write"]
        - sum(s["dur"] for s in spans if s["name"] == "fsync" and "parent" in s)
    )


def test_unarmed_no_span_is_allocated_and_no_sync_is_added(
    sources, tmp_path, monkeypatch
):
    import jax

    def never(*args, **kwargs):
        raise AssertionError("span machinery ran with spans unarmed")

    monkeypatch.setattr(accounting.TaskScope, "add_span", never)
    monkeypatch.setattr(accounting, "_trace_annotation", never)
    # the streamed preload waits on its own updates before it rewrites a
    # staging buffer (``_Staging.release``): that wait is the route's, armed
    # or not, and is no ``jax.block_until_ready``
    monkeypatch.setattr(jax, "block_until_ready", never)
    cap = _store(sources, tmp_path, "c", [])
    assert not {"span_s", "span_self_s", "span_n"} & set(cap.stats)
    assert all(not e.spans and not e.spans_dropped for e in cap.events)
    assert cap.stats["host_syncs"] == 4


@pytest.mark.parametrize("armed", ["unarmed", "collector", "env"])
def test_store_bytes_are_counted_exactly_once(sources, tmp_path, monkeypatch, armed):
    if armed == "env":
        monkeypatch.setenv(accounting.SPANS_ENV_VAR, "1")
    callbacks = [TraceCollector(trace_dir=None)] if armed == "collector" else []
    stats = _store(sources, tmp_path, "c", callbacks).stats
    assert stats["bytes_read"] == 2 * NBYTES
    assert stats["bytes_written"] == NBYTES
    assert stats["chunks_read"] == 8 and stats["chunks_written"] == 4
    # a preload's and a flush's bytes now belong to an op
    rows = stats["per_op"].values()
    assert sum(r["bytes_read"] for r in rows) == 2 * NBYTES
    assert sum(r["bytes_written"] for r in rows) == NBYTES
    assert ("span_s" in stats) == (armed != "unarmed")


@pytest.mark.parametrize("query", ["store", "colmean"])
def test_transfer_counters_read_what_the_plan_implies(sources, tmp_path, query):
    cap = _Capture()
    if query == "store":
        ct.to_zarr(
            _add(sources), str(tmp_path / "c.zarr"),
            executor=JaxExecutor(), callbacks=[cap],
        )
        fetched, syncs = NBYTES, 4  # one fetch a chunk
    else:
        value = xp.mean(_add(sources), axis=0).compute(
            executor=JaxExecutor(), callbacks=[cap]
        )
        assert value.shape == (SIDE,)
        fetched, syncs = SIDE * 8, SIDE // CHUNK
    assert cap.stats["h2d_bytes"] == 2 * NBYTES
    assert cap.stats["d2h_bytes"] == fetched
    assert cap.stats["host_syncs"] == syncs


def test_flush_event_completes_no_task(sources, tmp_path):
    cap = _store(sources, tmp_path, "c", [TraceCollector(trace_dir=None)])
    (flush,) = [e for e in cap.events if e.chunk_key == "flush"]
    assert flush.num_tasks == 0 and flush.bytes_written == NBYTES
    assert {s["name"] for s in flush.spans} >= {"jax.flush", "jax.d2h", "fsync"}
    row = cap.stats["per_op"][flush.array_name]
    assert row["tasks"] == 4 and row["bytes_written"] == NBYTES
    assert cap.stats["tasks_completed"] == sum(e.num_tasks for e in cap.events)
    # the segment's spans ride one member op's event, not each
    carriers = [e for e in cap.events if e.spans and e.chunk_key != "flush"
                and any(s["name"] == "jax.dispatch" for s in e.spans)]
    assert len(carriers) == 1


@pytest.mark.parametrize("chips, streams", [(4, True), (8, False)],
                         ids=["a_chunk_a_chip_streams", "through_a_chunk_nests_under_h2d"])
def test_under_a_mesh_reads_are_recorded_beside_or_under_h2d(sources, tmp_path, chips, streams):
    """Four chips take the 2 x 2 grid a chunk each: the sources stream, a
    chunk's read is the sibling of its ``jax.h2d`` as without a mesh, and the
    span says which chip the chunk went to. Eight cut an axis four ways,
    through the chunks: the callback's reads nest under the one ``jax.h2d``
    of a source, which names no chip."""
    import jax

    from cubed_tpu.parallel.mesh import make_mesh

    tc = TraceCollector(trace_dir=None)
    executor = JaxExecutor(mesh=make_mesh(devices=jax.devices()[:chips]))
    stats = _store(sources, tmp_path, "c", [tc], executor=executor).stats
    reads, chips_named, chips_left = 0, [], []
    for rec in tc._records:
        by_id = {s["id"]: s for s in rec["spans"]}
        for s in rec["spans"]:
            if s["name"] == "storage_read":
                reads += 1
                assert by_id[s["parent"]]["name"] == ("jax.preload" if streams else "jax.h2d")
            if s["name"] == "jax.h2d" and "device" in s["attrs"]:
                chips_named.append(s["attrs"]["device"])
            if s["name"] == "jax.d2h":
                chips_left.append(s["attrs"].get("device"))
    assert reads >= 8
    assert stats["span_n"]["storage_read"] == reads
    if streams:
        assert sorted(chips_named) == sorted([d.id for d in jax.devices()[:4]] * 2)
        assert stats["span_n"]["jax.h2d"] == 8
        # and each chunk of the target left from the chip that holds it
        assert sorted(chips_left) == sorted(d.id for d in jax.devices()[:4])
    else:
        assert chips_named == [] and stats["span_n"]["jax.h2d"] == 2
        # a chunk that crosses shards is fetched from them all: no one chip
        assert chips_left == [None] * 4
        assert stats["span_self_s"]["jax.h2d"] < stats["span_s"]["jax.h2d"]
    np.testing.assert_allclose(
        ct.from_zarr(str(tmp_path / "c.zarr"), spec=sources[0]).compute(),
        sum(ct.from_zarr(p, spec=sources[0]).compute() for p in sources[1]),
        rtol=2**-44,
    )


def test_scope_names_show_in_debug_text_only_and_the_key_ignores_names(
    sources, tmp_path, monkeypatch
):
    import jax

    jxm._STRUCT_CACHE.clear()
    jxm._SEGMENT_CACHE.clear()
    lowered = []
    real_jit = jax.jit

    class SpyJit:
        def __init__(self, fn, *args, **kwargs):
            self._jitted = real_jit(fn, *args, **kwargs)

        def lower(self, *args, **kwargs):
            lowered.append(self._jitted.lower(*args, **kwargs))
            return lowered[-1]

        def __call__(self, *args, **kwargs):
            return self._jitted(*args, **kwargs)

    payloads = []
    monkeypatch.setattr(jxm, "_STRUCT_DEBUG", payloads)
    monkeypatch.setattr(jax, "jit", SpyJit)
    first = _store(sources, tmp_path, "first", []).stats
    monkeypatch.setattr(jax, "jit", real_jit)
    second = _store(sources, tmp_path, "second", []).stats
    # two plans of one shape with different array names: one key, one compile
    assert set(first["per_op"]) != set(second["per_op"])
    assert len(payloads) == 2 and payloads[0] == payloads[1]
    assert first["segments_compiled"] == 1
    assert second.get("segments_compiled", 0) == 0
    assert second["segment_struct_hits"] == second["segments_traced"] == 1
    (segment,) = [low for low in lowered if "op00." in low.as_text(debug_info=True)]
    assert "op00.blockwise" in segment.as_text(debug_info=True)
    assert "op00." not in segment.as_text()  # the cache keys read as before
    assert str(tmp_path) not in segment.as_text(debug_info=True)


def test_analyze_tiles_a_device_compute(sources, tmp_path):
    tc = TraceCollector(trace_dir=None)
    _store(sources, tmp_path, "c", [tc])
    report = analyze(tc)
    buckets = report.attribution
    assert sum(buckets.values()) == pytest.approx(report.wall_clock_s, abs=1e-4)
    assert buckets["transfer"] > 0 and buckets["storage_write"] > 0
    assert buckets["storage_read"] > 0 and buckets["dispatch_overhead"] > 0
    path = [(row["op"], row["chunk"]) for row in report.critical_path]
    assert path[-1][1] == "flush" and path[-2] == (path[-1][0], None)
    assert report.to_dict()["critical_path_source"] == "chunk_graph"


def test_armed_spans_are_profiler_annotations_too(sources, tmp_path, monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(accounting, "_TraceAnnotation", Annotation)
    _store(sources, tmp_path, "unarmed", [])
    assert entered == []
    _store(sources, tmp_path, "armed", [TraceCollector(trace_dir=None)])
    assert {"cubed:" + name for name in ZARR_ADD_SPANS} <= set(entered)


def test_plan_finalize_s_is_in_executor_stats(sources, tmp_path):
    stats = _store(sources, tmp_path, "c", []).stats
    assert 0 < stats["plan_finalize_s"] < 5


def test_an_eager_op_carries_its_own_spans(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB")
    a = ct.from_array(np.arange(16.0).reshape(4, 4), chunks=(2, 2), spec=spec)
    tc, cap = TraceCollector(trace_dir=None), _Capture()
    ct.to_zarr(
        a, str(tmp_path / "out.zarr"),
        executor=JaxExecutor(fuse_plan=False), callbacks=[tc, cap],
    )
    assert cap.stats["eager_ops"] > 0 and "jax.h2d" in cap.stats["span_n"]
    assert "jax.struct_key" not in cap.stats["span_n"]  # nothing was traced


def test_a_flush_of_many_chunks_drops_no_span(tmp_path):
    """A scope of this executor is a whole array's chunk IO: it has room for
    more spans than a task whose stats are shipped."""
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB")
    a = ct.from_array(np.ones((64, 64)), chunks=(8, 8), spec=spec)
    cap = _Capture()
    ct.to_zarr(
        xp.negative(a), str(tmp_path / "out.zarr"),
        executor=JaxExecutor(), callbacks=[cap, TraceCollector(trace_dir=None)],
    )
    assert cap.stats["span_n"]["jax.d2h"] == cap.stats["host_syncs"] == 64
    assert cap.stats["span_n"]["storage_write"] == 64
    assert 5 * 64 > accounting.MAX_TASK_SPANS and cap.stats["spans_dropped"] == 0


# -- who waited for whom, and whether the pages were fresh -----------------------


@pytest.mark.parametrize("counter", PIPELINE_COUNTERS)
def test_a_pipeline_counter_is_a_whole_number_and_zero_where_no_pipeline_ran(
    tmp_path, counter
):
    """A compute that preloads no source and flushes one 0-d chunk: each
    counter is there, a whole number, the same in the executor's ``stats``
    and in ``executor_stats``, and reads 0."""
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB")
    a = ct.from_array(np.arange(36.0).reshape(6, 6), chunks=(3, 3), spec=spec)
    executor, cap = JaxExecutor(), _Capture()
    assert float(xp.sum(a).compute(executor=executor, callbacks=[cap])) == 630.0
    assert counter in executor.stats and counter in cap.stats
    assert type(executor.stats[counter]) is int and type(cap.stats[counter]) is int
    assert executor.stats[counter] == cap.stats[counter]
    assert executor.stats[counter] == 0


def test_a_0d_flush_takes_its_checksum_and_no_writer(tmp_path):
    """The 0-d result's write takes no writer thread; its CRC-32 is counted
    all the same, by the store and under the one name: the executor keeps
    no count of its own beside the compute's."""
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB")
    a = ct.from_array(np.arange(36.0).reshape(6, 6), chunks=(3, 3), spec=spec)
    executor, cap = JaxExecutor(), _Capture()
    assert float(xp.sum(a).compute(executor=executor, callbacks=[cap])) == 630.0
    assert cap.stats["checksum_us"] > 0 and "checksum_us" not in executor.stats
    assert executor.stats["write_wait_us"] == 0


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "disarmed"])
def test_a_compute_whose_writes_are_slow_waits_for_its_writer(
    sources, tmp_path, monkeypatch, armed
):
    real = ZarrV2Array._write_chunk

    def write_chunk(self, *args, **kwargs):
        time.sleep(0.05)
        return real(self, *args, **kwargs)

    monkeypatch.setattr(ZarrV2Array, "_write_chunk", write_chunk)
    tc = TraceCollector(trace_dir=None)
    stats = _store(sources, tmp_path, "c", [tc] if armed else []).stats
    assert type(stats["write_wait_us"]) is int
    assert stats["write_wait_us"] >= (4 - 1) * 30_000
    if not armed:
        assert "span_n" not in stats
        return
    assert stats["span_n"]["jax.write_wait"] == 4 and stats["spans_dropped"] == 0
    spans = _spans(tc)
    (flush,) = [s for s in spans if s["name"] == "jax.flush"]
    waits = [s for s in spans if s["name"] == "jax.write_wait"]
    assert [s["attrs"]["chunk"] for s in waits] == [0, 1, 2, 3]
    assert all(s["parent"] == flush["id"] and s["cat"] == "wait" for s in waits)
    assert stats["span_s"]["jax.write_wait"] == pytest.approx(
        stats["write_wait_us"] / 1e6, abs=0.02
    )
    # the flush's own time is what its fetches and its waits leave of it
    assert stats["span_self_s"]["jax.flush"] < 0.05 < stats["span_s"]["jax.flush"]


@pytest.mark.parametrize("mode", ["write", "off"])
@pytest.mark.parametrize("make_executor", [JaxExecutor, PythonDagExecutor],
                         ids=["jax", "python"])
def test_checksum_us_times_the_crc_and_the_manifest_line_on_every_executor(
    sources, tmp_path, mode, make_executor
):
    """The store's own count, scoped: it reaches ``executor_stats`` from a
    task of any executor, the device executor's writer thread among them."""
    spec, paths = sources
    spec = ct.Spec(work_dir=spec.work_dir, allowed_mem="500MB", integrity=mode)
    executor = make_executor()
    stats = _store((spec, paths), tmp_path, "c", [], executor=executor).stats
    if mode == "off":
        assert stats.get("checksum_us", 0) == 0
    else:
        assert type(stats["checksum_us"]) is int and stats["checksum_us"] > 0


def test_h2d_and_storage_write_have_the_children_they_had(sources, tmp_path):
    """``h2d_s`` is ``jax.h2d``'s self time and ``fsync_s`` what
    ``storage_write``'s children cover: the wait for a staging buffer and the
    CRC-32 are counters, so that neither span gained a child."""
    tc = TraceCollector(trace_dir=None)
    stats = _store(sources, tmp_path, "c", [tc]).stats
    children = {}
    for rec in tc._records:
        by_id = {s["id"]: s for s in rec["spans"]}
        for s in rec["spans"]:
            if "parent" in s:
                children.setdefault(by_id[s["parent"]]["name"], set()).add(s["name"])
    assert "jax.h2d" not in children
    assert children["storage_write"] == {"fsync"}
    assert children["jax.preload"] == {"storage_read", "jax.h2d"}
    assert children["jax.flush"] == {
        "jax.device_wait", "jax.d2h", "jax.write_wait", "chunk_encode", "storage_write",
    }
    assert stats["span_self_s"]["jax.h2d"] == pytest.approx(stats["span_s"]["jax.h2d"])
    assert stats["checksum_us"] / 1e6 <= stats["span_self_s"]["storage_write"]
    # every wait of this preload lies in a chunk's ``jax.h2d``: none is the flush's
    waits = [s["attrs"]["wait_us"] for s in _spans(tc) if s["name"] == "jax.h2d"]
    assert stats["stage_wait_us"] == sum(waits) <= stats["span_s"]["jax.h2d"] * 1e6


# -- the generic pieces, on hand-made spans ---------------------------------


def _span(name, ts, dur, id, parent=None):
    s = {"name": name, "ts": ts, "dur": dur, "cat": "span", "id": id}
    if parent is not None:
        s["parent"] = parent
    return s


def test_aggregator_folds_self_time_per_task():
    from cubed_tpu.runtime.types import TaskEndEvent

    agg = _ComputeAggregator()
    for _ in range(2):  # ids repeat from task to task and must not mix
        agg.on_task_end(TaskEndEvent(
            array_name="op", num_tasks=0, spans_dropped=3,
            spans=[_span("inner", 1.0, 0.25, 1, parent=0), _span("outer", 0.5, 1.0, 0)],
        ))
    out = agg.summary()
    assert out["span_s"] == {"inner": 0.5, "outer": 2.0}
    assert out["span_self_s"] == {"inner": 0.5, "outer": 1.5}
    assert out["span_n"] == {"inner": 2, "outer": 2}
    assert out["spans_dropped"] == 6
    assert "span_s" not in _ComputeAggregator().summary()


def test_self_time_leaves_out_what_another_thread_did_under_a_span():
    """A flush on two threads: the writer's spans hang under ``jax.flush``
    and overlap its own children. Its self time is its duration less what its
    own thread did inside it, however long the writer worked; the writer's
    spans nest among themselves as ever; the sums by name are the sums."""
    from cubed_tpu.runtime.types import TaskEndEvent

    def on(thread, span):
        return {**span, "attrs": {"thread": thread}}

    agg = _ComputeAggregator()
    agg.on_task_end(TaskEndEvent(array_name="op", num_tasks=0, spans=[
        _span("jax.flush", 0.0, 1.0, 0),
        _span("jax.d2h", 0.0, 0.25, 1, parent=0),
        _span("jax.write_wait", 0.25, 0.5, 2, parent=0),
        on("cubed-tpu-flush_0", _span("storage_write", 0.25, 0.5, 3, parent=0)),
        on("cubed-tpu-flush_0", _span("fsync", 0.5, 0.125, 4, parent=3)),
        on("cubed-tpu-flush_0", _span("storage_write", 0.75, 0.5, 5, parent=0)),
    ]))
    out = agg.summary()
    assert out["span_s"]["jax.flush"] == 1.0 and out["span_s"]["storage_write"] == 1.0
    assert out["span_self_s"]["jax.flush"] == 0.25  # not max(0, 1.0 - 1.75)
    assert out["span_self_s"]["storage_write"] == 0.875
    assert out["span_self_s"]["fsync"] == 0.125
    assert out["span_self_s"]["jax.write_wait"] == 0.5


def test_a_span_across_task_boundaries_is_cut_between_them():
    def task(start, end):
        return {"op": "x", "chunk": None, "tid": 1, "start": start, "end": end,
                "dur": end - start}

    tasks = [task(0.0, 1.0), task(1.0, 3.0)]
    spans = [
        {"name": "jax.h2d", "start": 0.5, "end": 2.5, "tid": 1, "chunk": None,
         "id": 0, "parent": None},
        {"name": "storage_read", "start": 0.75, "end": 1.5, "tid": 1,
         "chunk": None, "id": 1, "parent": 0},
        {"name": "fsync", "start": 2.6, "end": 2.7, "tid": 1, "chunk": None,
         "id": 2, "parent": None},
    ]
    _attach_spans(tasks, spans)
    first, second = (_interior_buckets(t) for t in tasks)
    assert first == pytest.approx({"transfer": 0.25, "storage_read": 0.25})
    assert second == pytest.approx(
        {"transfer": 1.0, "storage_read": 0.5, "storage_write": 0.1}
    )
