"""The reduction from a device trace to metrics, on a small trace whose
answers are worked out by hand (times in nanoseconds):

host spans    compute [1000, 11000] and [21000, 31000]; in the first,
              execute_dag [2000, 10500], _run_segment [2500, 3500],
              _to_host.ready [4000, 9000], _to_host [9000, 9500]; in the
              second, execute_dag [22000, 30500]
chip 0        fusion.1 [3000, 7000], all-reduce.1 [7000, 8000], while.1
              [8000, 9000] with fusion.2 [8200, 8700] nested in it; fusion.1
              [15000, 16000] between the computes (not counted); fusion.1
              [23000, 27000]; fusion.3 [30500, 31500], half of it inside
chip 1        fusion.1 [3000, 5000], all-reduce.1 [7000, 8000], fusion.1
              [23000, 25000]

window   2 x 10000 = 20000
busy     chip 0: 6000 + 4000 + 500 = 10500; chip 1: 2000 + 1000 + 2000 = 5000
gaps     chip 0: [1000, 3000], [9000, 11000], [21000, 23000], [27000, 30500],
         each piece named after the shortest host span that covers it:
         compute 1000 + 500 + 1000, execute_dag 500 + 1000 + 1000 + 3500,
         _run_segment 500, _to_host 500; together 9500 = 20000 - 10500
"""

import json
import os

import pytest

from benchmark.harness import peaks, trace_reduce
from benchmark.harness.spans import ANNOTATION_PREFIX

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")
NS = 1e-9


@pytest.fixture(scope="module")
def reduced():
    with open(DATA) as f:
        return trace_reduce.reduce_trace(json.load(f), ANNOTATION_PREFIX)


def test_window_and_busy(reduced):
    assert reduced["computes"] == 2
    assert reduced["window_s"] == pytest.approx(20000 * NS)
    assert reduced["busy_s"] == {0: pytest.approx(10500 * NS), 1: pytest.approx(5000 * NS)}
    assert reduced["busiest"] == 0
    idle_share = 1 - reduced["busy_s"][0] / reduced["window_s"]
    assert idle_share == pytest.approx(0.475)


def test_per_chip_share_and_collectives(reduced):
    busy = reduced["busy_s"]
    assert min(busy.values()) / max(busy.values()) == pytest.approx(5000 / 10500)
    assert reduced["collective_s"] == {0: pytest.approx(1000 * NS), 1: pytest.approx(1000 * NS)}


def test_top_operations_are_self_times_of_the_busiest_chip(reduced):
    ops = dict(reduced["device_ops"])
    assert ops == {
        "fusion.1": pytest.approx(8000 * NS),  # the one between computes is left out
        "all-reduce.1": pytest.approx(1000 * NS),
        "fusion.3": pytest.approx(1000 * NS),
        "while.1": pytest.approx(500 * NS),  # less the fusion nested in it
        "fusion.2": pytest.approx(500 * NS),
    }
    assert reduced["device_ops"][0][0] == "fusion.1"


def test_idle_gaps_are_blamed_on_the_innermost_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps == {
        "JaxExecutor.execute_dag": pytest.approx(6000 * NS),
        "compute": pytest.approx(2500 * NS),
        "JaxExecutor._run_segment": pytest.approx(500 * NS),
        "JaxExecutor._to_host": pytest.approx(500 * NS),
    }
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"][0]
    )
    assert reduced["idle_gaps"][0][0] == "JaxExecutor.execute_dag"


def test_a_trace_without_device_or_computes_reduces_to_nothing():
    with open(DATA) as f:
        trace = json.load(f)
    host_only = {"planes": [p for p in trace["planes"] if not p["name"].startswith("/device")]}
    assert trace_reduce.reduce_trace(host_only, ANNOTATION_PREFIX) is None
    assert trace_reduce.reduce_trace(trace, "other:") is None


def test_a_plane_without_an_xla_ops_line_falls_back_to_its_other_lines(reduced):
    with open(DATA) as f:
        trace = json.load(f)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                line["name"] = "TensorCore"  # "XLA Modules" stays a summary
    again = trace_reduce.reduce_trace(trace, ANNOTATION_PREFIX)
    assert again["busy_s"] == reduced["busy_s"]
    assert again["device_ops"] == reduced["device_ops"]


def test_an_operation_is_named_by_what_stands_before_the_equals_sign():
    # as a v5e trace names them (my chip run, PR 24): the whole instruction
    long = ("%select_select_fusion.8 = (f32[180,100,100,100]{1,3,2,0:T(8,128)}) "
            "fusion(u32[180]{0:T(256)S(1)} %xor_xor_fusion.2, f32[4]{0} %all-reduce.3)")
    assert trace_reduce.short_name(long) == "select_select_fusion.8"
    assert not trace_reduce.COLLECTIVE.search(trace_reduce.short_name(long))
    assert trace_reduce.COLLECTIVE.search(trace_reduce.short_name("%all-reduce.3 = f32[4]{0} all-reduce(...)"))
    assert trace_reduce.short_name("bench:compute") == "bench:compute"


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert trace_reduce.total([(1, 4), (5, 7)]) == 5
    assert trace_reduce.clip([(1, 4), (5, 7)], [(3, 6)]) == [(3, 4), (5, 6)]
    assert trace_reduce.subtract([(0, 10)], [(1, 4), (5, 7)]) == [(0, 1), (4, 5), (7, 10)]
    assert trace_reduce.self_times([["a", 0, 10], ["b", 2, 3], ["c", 6, 2], ["a", 20, 5]]) == {
        "a": pytest.approx(10 * NS), "b": pytest.approx(3 * NS), "c": pytest.approx(2 * NS),
    }


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
