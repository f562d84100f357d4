"""Telemetry endpoint tests: Prometheus text-format conformance, the
stdlib-HTTP endpoints, arming precedence, and a live-fleet scrape."""

from __future__ import annotations

import json
import re
import tempfile
import threading
import time
import urllib.request

import numpy as np
import pytest

import cubed_tpu as ct
from cubed_tpu.observability import export
from cubed_tpu.observability.export import (
    TELEMETRY_PORT_ENV_VAR,
    TelemetryRuntime,
    escape_label_value,
    prometheus_text,
    resolve_port,
    sanitize_metric_name,
)
from cubed_tpu.observability.metrics import MetricsRegistry
from cubed_tpu.observability.timeseries import TimeSeriesStore

# ---------------------------------------------------------------------------
# exposition-format conformance
# ---------------------------------------------------------------------------

#: one sample line of text exposition format 0.0.4:
#: name{labels} value [timestamp]
_SAMPLE_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"               # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\\\|\\\"|\\n)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\\\|\\\"|\\n)*\")*\})?"
    r" -?[0-9.eE+\-naif]+$"                    # value (incl. 4e-05, nan/inf)
)


def parse_exposition(text: str) -> dict:
    """Strict parse of the exposition text: every line must be a comment
    or a valid sample; returns {sample_name_with_labels: float}."""
    samples = {}
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "summary", "histogram"), line
            types[name] = kind
        else:
            assert _SAMPLE_LINE.match(line), f"malformed sample line: {line!r}"
            key, _, value = line.rpartition(" ")
            samples[key] = float(value)
    # every sample belongs to a family that declared a TYPE
    for key in samples:
        base = key.split("{")[0]
        family_ok = any(
            base == name or base.startswith(name + "_")
            or name.startswith(base)
            for name in types
        )
        assert family_ok, f"sample {key!r} has no TYPE line"
    return samples


def test_metric_name_sanitization():
    assert sanitize_metric_name("foo.bar-baz") == "foo_bar_baz"
    assert sanitize_metric_name("a b/c") == "a_b_c"
    assert sanitize_metric_name("9lives") == "_9lives"
    assert sanitize_metric_name("already_fine:total") == "already_fine:total"


def test_label_value_escaping():
    assert escape_label_value('say "hi"') == 'say \\"hi\\"'
    assert escape_label_value("back\\slash") == "back\\\\slash"
    assert escape_label_value("two\nlines") == "two\\nlines"


def test_prometheus_text_help_type_and_values():
    reg = MetricsRegistry()
    reg.counter("tasks_completed").inc(12)
    reg.gauge("queue_depth").set(4)
    reg.histogram("op_wall_clock_s").observe(0.5)
    reg.histogram("op_wall_clock_s").observe(1.5)
    text = prometheus_text(registry=reg)
    samples = parse_exposition(text)
    assert samples["cubed_tpu_tasks_completed"] == 12
    assert samples["cubed_tpu_queue_depth"] == 4
    assert samples["cubed_tpu_queue_depth_max"] == 4
    assert samples["cubed_tpu_op_wall_clock_s_count"] == 2
    assert samples["cubed_tpu_op_wall_clock_s_sum"] == 2.0
    assert 'cubed_tpu_op_wall_clock_s{quantile="0.5"}' in samples
    assert 'cubed_tpu_op_wall_clock_s{quantile="0.99"}' in samples
    assert "# HELP cubed_tpu_tasks_completed" in text
    assert "# TYPE cubed_tpu_tasks_completed counter" in text
    assert "# TYPE cubed_tpu_queue_depth gauge" in text
    assert "# TYPE cubed_tpu_op_wall_clock_s summary" in text


def test_prometheus_text_sanitizes_weird_names_and_labels():
    reg = MetricsRegistry()
    reg.counter("weird.name-with/stuff").inc(1)
    store = TimeSeriesStore()
    store.record(
        "worker_rss_bytes", 7,
        labels={"worker": 'host:1 "quoted"\nnewline'},
    )
    text = prometheus_text(registry=reg, store=store)
    samples = parse_exposition(text)
    assert samples["cubed_tpu_weird_name_with_stuff"] == 1
    labelled = [k for k in samples if k.startswith("cubed_tpu_worker_rss_bytes{")]
    assert labelled, text
    assert '\\"quoted\\"' in labelled[0] and "\\n" in labelled[0]


def test_scrape_twice_counters_are_monotonic():
    reg = MetricsRegistry()
    reg.counter("tasks_completed").inc(3)
    reg.counter("task_retries").inc(1)
    first = parse_exposition(prometheus_text(registry=reg))
    reg.counter("tasks_completed").inc(5)
    second = parse_exposition(prometheus_text(registry=reg))
    kinds = reg.kinds()
    for name, kind in kinds.items():
        if kind != "counter":
            continue
        key = f"cubed_tpu_{name}"
        assert second[key] >= first[key], (
            f"counter {name} went backwards between scrapes"
        )
    assert second["cubed_tpu_tasks_completed"] == 8


def test_labelled_store_series_export_latest_sample():
    reg = MetricsRegistry()
    store = TimeSeriesStore()
    store.record("worker_outstanding", 1, ts=1.0, labels={"worker": "w0"})
    store.record("worker_outstanding", 4, ts=2.0, labels={"worker": "w0"})
    store.record("compute_tasks_done", 9, ts=2.0, labels={"compute": "c-1"})
    samples = parse_exposition(prometheus_text(registry=reg, store=store))
    assert samples['cubed_tpu_worker_outstanding{worker="w0"}'] == 4
    assert samples['cubed_tpu_compute_tasks_done{compute="c-1"}'] == 9


def test_fleet_aggregates_export_and_families_stay_unique():
    """Store-only series (the sampler's fleet aggregates) must appear on
    /metrics — they are what the documented alert thresholds read — and
    labelled samples must merge into an existing registry family instead
    of re-declaring it (one TYPE line per family, per the exposition
    spec). Registry-mirrored and histogram-derived unlabelled series must
    NOT duplicate their families."""
    reg = MetricsRegistry()
    reg.gauge("worker_rss_bytes").set(111)
    reg.counter("tasks_completed").inc(5)
    reg.histogram("op_wall_clock_s").observe(0.5)
    store = TimeSeriesStore()
    store.record("fleet_pressured_fraction", 0.5)
    store.record("fleet_workers_live", 4)
    # registry mirror + histogram-derived mirror: already exported
    store.record("tasks_completed", 5)
    store.record("op_wall_clock_s_count", 1)
    # labelled samples of a registry gauge: same family, extra samples
    store.record("worker_rss_bytes", 222, labels={"worker": "w0"})
    text = prometheus_text(registry=reg, store=store)
    samples = parse_exposition(text)
    assert samples["cubed_tpu_fleet_pressured_fraction"] == 0.5
    assert samples["cubed_tpu_fleet_workers_live"] == 4
    assert samples["cubed_tpu_worker_rss_bytes"] == 111
    assert samples['cubed_tpu_worker_rss_bytes{worker="w0"}'] == 222
    type_lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE ")]
    assert len(type_lines) == len(set(type_lines)), (
        "duplicate TYPE declarations:\n" + "\n".join(type_lines)
    )
    # the unlabelled mirrors did not add second families
    assert type_lines.count("# TYPE cubed_tpu_tasks_completed counter") == 1
    assert not any("op_wall_clock_s_count" in ln for ln in type_lines)


# ---------------------------------------------------------------------------
# arming precedence: env (operator) > Spec > off
# ---------------------------------------------------------------------------


def test_resolve_port_precedence(monkeypatch):
    monkeypatch.delenv(TELEMETRY_PORT_ENV_VAR, raising=False)
    assert resolve_port(None) is None
    spec = ct.Spec(telemetry_port=9100)
    assert resolve_port(spec) == 9100
    # env wins over Spec
    monkeypatch.setenv(TELEMETRY_PORT_ENV_VAR, "9200")
    assert resolve_port(spec) == 9200
    # the operator can force telemetry OFF even when a Spec arms it
    monkeypatch.setenv(TELEMETRY_PORT_ENV_VAR, "off")
    assert resolve_port(spec) is None
    monkeypatch.setenv(TELEMETRY_PORT_ENV_VAR, "")
    assert resolve_port(spec) is None
    # malformed env values stay loud
    monkeypatch.setenv(TELEMETRY_PORT_ENV_VAR, "not-a-port")
    with pytest.raises(ValueError):
        resolve_port(spec)
    monkeypatch.setenv(TELEMETRY_PORT_ENV_VAR, "70000")
    with pytest.raises(ValueError):
        resolve_port(spec)


def test_spec_validates_telemetry_port():
    assert ct.Spec(telemetry_port=0).telemetry_port == 0
    assert ct.Spec().telemetry_port is None
    with pytest.raises(ValueError):
        ct.Spec(telemetry_port=-1)
    with pytest.raises(ValueError):
        ct.Spec(telemetry_port=99999)


# ---------------------------------------------------------------------------
# the HTTP endpoints
# ---------------------------------------------------------------------------


def _get(port: int, path: str):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:  # non-200 still carries a body
        return e.code, e.read().decode(), dict(e.headers)


@pytest.fixture
def runtime():
    rt = TelemetryRuntime(port=0)
    rt.start()
    try:
        yield rt
    finally:
        rt.stop()


def test_endpoints_serve_metrics_healthz_snapshot(runtime):
    runtime.sampler.sample_once()
    code, body, headers = _get(runtime.port, "/metrics")
    assert code == 200
    assert headers["Content-Type"].startswith("text/plain")
    parse_exposition(body)  # must be valid exposition text
    assert "cubed_tpu_telemetry_samples" in body

    code, body, _ = _get(runtime.port, "/healthz")
    assert code == 200
    health = json.loads(body)
    assert health["status"] in ("ok", "degraded")
    assert health["sampler_alive"] in (True, False)
    assert health["last_sample_age_s"] is not None

    code, body, _ = _get(runtime.port, "/snapshot.json")
    assert code == 200
    snap = json.loads(body)
    for key in ("ts", "metrics", "fleet", "computes", "alerts", "series"):
        assert key in snap

    code, _, _ = _get(runtime.port, "/nope")
    assert code == 404


def test_healthz_reports_stale_sampler_as_503():
    rt = TelemetryRuntime(port=0)
    rt.start()
    try:
        rt.sampler.stop()
        rt.sampler.last_sample_ts = time.time() - 60.0
        code, body, _ = _get(rt.port, "/healthz")
        assert code == 503
        assert json.loads(body)["status"] == "stale"
    finally:
        rt.stop()


def test_bind_host_env_knob(monkeypatch):
    from cubed_tpu.observability.export import TELEMETRY_HOST_ENV_VAR

    monkeypatch.setenv(TELEMETRY_HOST_ENV_VAR, "127.0.0.1")
    rt = TelemetryRuntime(port=0)
    rt.start()
    try:
        assert rt.server.server_address[0] == "127.0.0.1"
        code, _, _ = _get(rt.port, "/healthz")
        assert code in (200, 503)
    finally:
        rt.stop()


def test_ensure_started_is_idempotent_singleton(monkeypatch):
    export.shutdown()
    try:
        rt1 = export.ensure_started(0)
        rt2 = export.ensure_started(0)
        assert rt1 is rt2
        assert export.get_runtime() is rt1
        # a conflicting port request is logged and ignored, not a rebind
        rt3 = export.ensure_started(12345)
        assert rt3 is rt1
    finally:
        export.shutdown()
    assert export.get_runtime() is None


# ---------------------------------------------------------------------------
# live fleet scrape: /metrics + /healthz answered DURING a distributed
# compute (fleet workers are real subprocesses)
# ---------------------------------------------------------------------------


def test_live_fleet_compute_serves_metrics_and_healthz(tmp_path, monkeypatch):
    import weakref

    from cubed_tpu.observability import timeseries
    from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor
    from tests.utils import SlowAdd

    # /healthz counts the workers of every live fleet of the process: a
    # fleet that an earlier test file of this xdist worker left open would
    # be counted with this test's two workers
    monkeypatch.setattr(timeseries, "_fleets", weakref.WeakSet())
    export.shutdown()
    spec = ct.Spec(
        work_dir=str(tmp_path), allowed_mem="500MB", telemetry_port=0
    )
    an = np.arange(64.0).reshape(8, 8)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    r = ct.map_blocks(SlowAdd(0.15), a, dtype=np.float64)
    ex = DistributedDagExecutor(n_local_workers=2)
    result_box: dict = {}

    def compute():
        try:
            result_box["value"] = np.asarray(r.compute(executor=ex))
        except BaseException as e:  # surfaced by the main thread
            result_box["error"] = e

    t = threading.Thread(target=compute)
    try:
        ex._ensure_fleet()
        t.start()
        # wait for the compute to arm telemetry, then scrape it LIVE
        deadline = time.monotonic() + 30
        rt = None
        while rt is None and time.monotonic() < deadline:
            rt = export.get_runtime()
            time.sleep(0.02)
        assert rt is not None, "telemetry never armed"
        code, metrics_body, _ = _get(rt.port, "/metrics")
        assert code == 200
        parse_exposition(metrics_body)
        code, health_body, _ = _get(rt.port, "/healthz")
        health = json.loads(health_body)
        assert code in (200, 503)  # first sample may still be pending
        t.join(timeout=120)
        assert not t.is_alive()
        assert "error" not in result_box, result_box.get("error")
        np.testing.assert_array_equal(result_box["value"], an + 1.0)
        # after the compute: the fleet was visible and metrics flowed
        rt.sampler.sample_once()
        code, body, _ = _get(rt.port, "/metrics")
        samples = parse_exposition(body)
        assert samples.get("cubed_tpu_tasks_completed", 0) >= 16
        code, body, _ = _get(rt.port, "/healthz")
        health = json.loads(body)
        assert health["workers_live"] == 2
        snap = json.loads(_get(rt.port, "/snapshot.json")[1])
        assert any(
            c.get("status") == "succeeded" and c.get("tasks_done") ==
            c.get("tasks_total") for c in snap["computes"]
        ), snap["computes"]
        # the dashboard renders a frame from the same compute's endpoint
        from cubed_tpu import top

        frame = top.render(top.fetch_snapshot(f"127.0.0.1:{rt.port}"))
        assert "local-0" in frame and "local-1" in frame
        assert "succeeded" in frame
    finally:
        ex.close()
        export.shutdown()
