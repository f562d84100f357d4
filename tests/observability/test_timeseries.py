"""Time-series store + telemetry sampler unit tests."""

from __future__ import annotations

import time
import weakref

import pytest

from cubed_tpu.observability import timeseries
from cubed_tpu.observability.metrics import MetricsRegistry, get_registry
from cubed_tpu.observability.timeseries import (
    ComputeProgressCallback,
    TelemetrySampler,
    TimeSeriesStore,
    _computes,
    _computes_lock,
    compute_progress,
    fleet_view,
    live_fleets,
    register_fleet,
    unregister_fleet,
)


@pytest.fixture(autouse=True)
def _own_fleet_and_service_registries(monkeypatch):
    """The sampler reads process-global registries of live fleets and
    services; a fleet that an earlier test file of this xdist worker left
    open (weakly held, not yet collected) would be counted with the fake
    one here. Every test of this file sees registries of its own."""
    monkeypatch.setattr(timeseries, "_fleets", weakref.WeakSet())
    monkeypatch.setattr(timeseries, "_services", weakref.WeakSet())


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------


def test_store_record_latest_and_window():
    s = TimeSeriesStore()
    t0 = 1000.0
    for i in range(5):
        s.record("m", i, ts=t0 + i)
    assert s.latest("m") == 4
    # trailing 2.5s window from t0+4 holds the last 3 points
    pts = s.window("m", 2.5, now=t0 + 4)
    assert [v for _, v in pts] == [2, 3, 4]
    assert s.window("missing", 10, now=t0) == []
    assert s.latest("missing") is None


def test_store_labels_are_distinct_series():
    s = TimeSeriesStore()
    s.record("rss", 1, ts=1.0, labels={"worker": "a"})
    s.record("rss", 2, ts=1.0, labels={"worker": "b"})
    assert s.latest("rss", labels={"worker": "a"}) == 1
    assert s.latest("rss", labels={"worker": "b"}) == 2
    # labelled series surface for the Prometheus exposition
    labelled = {
        (name, labels["worker"]): v
        for name, labels, v in s.labelled_latest()
    }
    assert labelled[("rss", "a")] == 1 and labelled[("rss", "b")] == 2


def test_store_ring_is_bounded_per_series():
    s = TimeSeriesStore(capacity=10)
    for i in range(100):
        s.record("m", i, ts=float(i))
    pts = s.window("m", 1e9, now=100.0)
    assert len(pts) == 10
    assert pts[-1][1] == 99  # newest kept, oldest evicted


def test_store_series_cap_evicts_stalest_for_new():
    reg = get_registry()
    before = reg.snapshot()
    s = TimeSeriesStore(max_series=3)
    # stalest-last-point series make way for new ones (a long-lived
    # endpoint churns compute/worker labels forever; dropping the NEW
    # series would starve exactly what the operator is watching)
    for i in range(6):
        s.record("m", 1, ts=float(i), labels={"worker": f"w{i}"})
    assert len(s.series()) == 3
    kept = {labels["worker"] for _, labels, _ in s.latest_series()}
    assert kept == {"w3", "w4", "w5"}  # the freshest survive
    assert s.series_evicted == 3
    delta = reg.snapshot_delta(before)
    assert delta.get("timeseries_series_evicted", 0) >= 3


def test_store_rate_from_cumulative_counter():
    s = TimeSeriesStore()
    s.record("c", 10, ts=100.0)
    s.record("c", 30, ts=110.0)
    assert s.rate("c", 60, now=110.0) == 2.0
    # counter reset (process restart) must clamp to zero, not go negative
    s.record("c", 0, ts=120.0)
    assert s.rate("c", 60, now=120.0) == 0.0
    # a single point has no rate
    s2 = TimeSeriesStore()
    s2.record("c", 1, ts=1.0)
    assert s2.rate("c", 60, now=1.0) is None


def test_store_ignores_non_numeric_values():
    s = TimeSeriesStore()
    s.record("m", "not-a-number", ts=1.0)
    s.record("m", None, ts=1.0)
    s.record("m", True, ts=2.0)  # bools coerce to 0/1
    assert s.latest("m") == 1


def test_store_to_dict_windows_and_bounds():
    s = TimeSeriesStore()
    for i in range(50):
        s.record("m", i, ts=1000.0 + i, labels={"worker": "a"})
    rows = s.to_dict(window_s=20.0, max_points=5, now=1049.0)
    assert len(rows) == 1
    row = rows[0]
    assert row["name"] == "m" and row["labels"] == {"worker": "a"}
    assert len(row["points"]) == 5
    assert row["points"][-1][1] == 49


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------


def test_sampler_records_registry_counters_gauges_histograms(monkeypatch):
    reg = MetricsRegistry()
    reg.counter("tasks_completed").inc(7)
    reg.gauge("queue_depth").set(3)
    reg.histogram("op_wall_clock_s").observe(0.5)
    monkeypatch.setattr(
        "cubed_tpu.observability.timeseries.get_registry", lambda: reg
    )
    store = TimeSeriesStore()
    sampler = TelemetrySampler(store)
    sampler.sample_once(now=100.0)
    assert store.latest("tasks_completed") == 7
    assert store.latest("queue_depth") == 3
    assert store.latest("op_wall_clock_s_count") == 1
    assert store.latest("op_wall_clock_s_sum") == 0.5
    assert store.latest("op_wall_clock_s_p50") == 0.5
    # the tick itself is counted (on the patched registry)
    assert reg.snapshot().get("telemetry_samples") == 1
    assert sampler.last_sample_ts == 100.0


class _FakeCoordinator:
    """The minimal coordinator surface the sampler/fleet_view read."""

    def __init__(self, rows, workers):
        self._rows = rows
        self._workers = workers
        import threading

        self._closed = threading.Event()

    def load_view(self):
        return self._rows

    def stats_snapshot(self):
        return {"workers": self._workers}


def _fake_fleet():
    return _FakeCoordinator(
        rows=[
            {"name": "w0", "draining": False, "pressured": True,
             "connected": True, "outstanding": 2, "nthreads": 1},
            {"name": "w1", "draining": False, "pressured": False,
             "connected": True, "outstanding": 1, "nthreads": 1},
        ],
        workers={
            "w0": {"alive": True, "connected": True, "pressured": True,
                   "rss": 1024, "peer_cache": {"bytes": 10},
                   "metrics": {"worker_tasks_executed": 5}},
            "w1": {"alive": True, "connected": True, "pressured": False,
                   "rss": 2048, "peer_cache": None, "metrics": None},
        },
    )


def test_sampler_records_fleet_series_per_worker_and_aggregate():
    coord = _fake_fleet()
    register_fleet(coord)
    try:
        store = TimeSeriesStore()
        TelemetrySampler(store).sample_once(now=50.0)
        assert store.latest("fleet_workers_live") == 2
        assert store.latest("fleet_workers_pressured") == 1
        assert store.latest("fleet_pressured_fraction") == 0.5
        assert store.latest("fleet_queue_depth") == 3
        assert store.latest(
            "worker_rss_bytes", labels={"worker": "w0"}
        ) == 1024
        assert store.latest(
            "worker_outstanding", labels={"worker": "w1"}
        ) == 1
        assert store.latest(
            "fleet_worker_tasks_executed", labels={"worker": "w0"}
        ) == 5
        view = fleet_view()
        assert view["workers_live"] == 2
        assert view["workers_pressured"] == 1
        assert "w0" in view["workers"]
    finally:
        unregister_fleet(coord)


def test_fleet_registration_is_weak_and_close_aware():
    coord = _fake_fleet()
    register_fleet(coord)
    assert coord in live_fleets()
    coord._closed.set()
    assert coord not in live_fleets()
    unregister_fleet(coord)
    # a dropped reference disappears from the registry on its own
    coord2 = _fake_fleet()
    register_fleet(coord2)
    del coord2
    import gc

    gc.collect()
    assert all(c is not None for c in live_fleets())


# ---------------------------------------------------------------------------
# compute progress
# ---------------------------------------------------------------------------


class _Event:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _fake_dag(num_tasks=4):
    import networkx as nx

    class _Op:
        def __init__(self, n):
            self.num_tasks = n

    dag = nx.MultiDiGraph()
    dag.add_node("op-a", type="op", primitive_op=_Op(num_tasks))
    return dag


def test_compute_progress_callback_tracks_done_total_and_status():
    with _computes_lock:
        _computes.clear()
    cb = ComputeProgressCallback()
    cb.on_compute_start(_Event(compute_id="c-test", dag=_fake_dag(3)))
    rows = compute_progress()
    assert rows[-1]["compute_id"] == "c-test"
    assert rows[-1]["tasks_total"] == 3
    assert rows[-1]["status"] == "running"
    for _ in range(2):
        cb.on_task_end(_Event())
    assert compute_progress()[-1]["tasks_done"] == 2
    cb.on_compute_end(_Event(error=None))
    row = compute_progress()[-1]
    assert row["status"] == "succeeded" and row["ended_at"] is not None
    # a failed compute reads as failed
    cb2 = ComputeProgressCallback()
    cb2.on_compute_start(_Event(compute_id="c-fail", dag=_fake_dag(1)))
    cb2.on_compute_end(_Event(error=RuntimeError("boom")))
    assert compute_progress()[-1]["status"] == "failed"


def test_compute_progress_feeds_sampler_series():
    with _computes_lock:
        _computes.clear()
    cb = ComputeProgressCallback()
    cb.on_compute_start(_Event(compute_id="c-live", dag=_fake_dag(10)))
    cb.on_task_end(_Event())
    store = TimeSeriesStore()
    TelemetrySampler(store).sample_once(now=10.0)
    assert store.latest(
        "compute_tasks_done", labels={"compute": "c-live"}
    ) == 1
    assert store.latest(
        "compute_tasks_total", labels={"compute": "c-live"}
    ) == 10
    cb.on_compute_end(_Event(error=None))
    # finished computes stop being sampled (series freezes)
    TelemetrySampler(store).sample_once(now=11.0)
    pts = store.window("compute_tasks_done", 100, labels={"compute": "c-live"}, now=11.0)
    assert len(pts) == 1


def test_sampler_thread_lifecycle():
    store = TimeSeriesStore()
    sampler = TelemetrySampler(store, interval_s=0.05)
    sampler.start()
    try:
        deadline = time.monotonic() + 5.0
        while sampler.last_sample_ts is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sampler.last_sample_ts is not None
        assert sampler.alive
    finally:
        sampler.stop()
    assert not sampler.alive
    # a stopped sampler restarts cleanly (stop() must not poison start())
    sampler.last_sample_ts = None
    sampler.start()
    try:
        deadline = time.monotonic() + 5.0
        while sampler.last_sample_ts is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sampler.alive and sampler.last_sample_ts is not None
    finally:
        sampler.stop()


def test_fleet_aggregates_decay_to_zero_after_fleet_closes():
    """A closed fleet's last pressured reading must not freeze: the
    aggregates keep recording real zeros so a pressure alert clears."""
    coord = _fake_fleet()
    register_fleet(coord)
    store = TimeSeriesStore()
    sampler = TelemetrySampler(store)
    try:
        sampler.sample_once(now=50.0)
        assert store.latest("fleet_pressured_fraction") == 0.5
    finally:
        coord._closed.set()
        unregister_fleet(coord)
    sampler.sample_once(now=51.0)
    assert store.latest("fleet_pressured_fraction") == 0.0
    assert store.latest("fleet_workers_live") == 0
