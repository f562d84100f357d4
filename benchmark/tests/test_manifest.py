"""``BENCHMARK.json`` against the contract, in the sandbox: the check passes
on the tree as committed, and finds each kind of breach that has stopped, or
could stop, a PR before any run (PR 23 was refused over one ``source``)."""

import json
import os

import pytest

from benchmark.harness import manifest


def test_the_manifest_meets_the_contract():
    assert manifest.check() == []


def test_every_text_is_plain_ascii_and_counted():
    bench = manifest.load()
    texts = [c[k] for c in bench["configs"] for k in ("source", "why")]
    texts += [w["why"] for w in bench["workloads"]]
    texts += [x["layer"] for x in bench["per_layer"]]
    for text in texts:
        assert 1 <= len(text) <= 200, text
        assert all(0x20 <= ord(ch) <= 0x7E for ch in text), text


def _metric(bench, group, name):
    return next(x for x in bench[group] if x["name"] == name)


def _non_ascii_source(b):
    b["configs"][1]["source"] = b["configs"][1]["source"].replace("x", "×", 1)


def _long_why(b):
    b["workloads"][0]["why"] = "w" * 201


def _unit_with_space(b):
    _metric(b, "end_to_end", "compute_s")["unit"] = "s per compute"


def _name_with_slash(b):
    b["workloads"][0]["name"] = "vorticity/mean"


def _window_too_long(b):
    b["run_seconds"] = 52


def _window_too_short(b):
    b["run_seconds"] = 5


def _too_many_on_four_chips(b):
    for w in b["workloads"][:3]:
        w["chips"] = 4


def _config_without_a_cell(b):
    b["configs"].append(dict(b["configs"][0], name="unused-config"))


def _traffic_file_missing(b):
    b["workloads"][0]["traffic"] = "no_such_mix.closed-1"


def _moves_a_metric_the_cell_lacks(b):
    _metric(b, "per_layer", "preload_s")["moves"] = "compute_s"


def _extra_key_on_a_metric(b):
    _metric(b, "per_layer", "plan_s")["why"] = "not allowed here"


def _bound_too_wide(b):
    _metric(b, "end_to_end", "zarr_compute_s")["bound"] = 0.3


def _bound_under_one_percent(b):
    _metric(b, "end_to_end", "compute_s")["bound"] = 0.005


def _no_setup_s(b):
    b["end_to_end"] = [x for x in b["end_to_end"] if x["name"] != "setup_s"]


def _same_name_twice(b):
    b["per_layer"].append(dict(_metric(b, "per_layer", "plan_s")))


def _pair_twice(b):
    again = dict(b["workloads"][0], name="vorticity.mean2")
    b["workloads"].append(again)
    for x in b["end_to_end"] + b["per_layer"]:
        if "vorticity.mean" in x.get("workloads", []):
            x["workloads"].append("vorticity.mean2")


def _reduced_names_a_width(b):
    b["configs"][0]["reduced"] = ["hidden_size"]


def _unknown_source_kind(b):
    _metric(b, "per_layer", "plan_s")["source"] = "stopwatch"


def _command_outside_paths(b):
    b["command"] = ["python3", "chip_smoke.py"]


def _extra_top_level_key(b):
    b["notes"] = "none"


def _reader_disagrees(b):
    _metric(b, "per_layer", "fetch_s")["layer"] = "some other layer"


def _metric_without_a_reader(b):
    b["per_layer"].append(dict(_metric(b, "per_layer", "plan_s"), name="no_such_metric"))


def _cell_reports_only_setup(b):
    _metric(b, "end_to_end", "compute_s")["workloads"] = ["zarr-add.store"]


BREACHES = [
    _non_ascii_source, _long_why, _unit_with_space, _name_with_slash,
    _window_too_long, _window_too_short, _too_many_on_four_chips,
    _config_without_a_cell, _traffic_file_missing,
    _moves_a_metric_the_cell_lacks, _extra_key_on_a_metric, _bound_too_wide,
    _bound_under_one_percent, _no_setup_s, _same_name_twice, _pair_twice,
    _reduced_names_a_width, _unknown_source_kind, _command_outside_paths,
    _extra_top_level_key, _reader_disagrees, _metric_without_a_reader,
    _cell_reports_only_setup,
]


@pytest.mark.parametrize("breach", BREACHES, ids=lambda f: f.__name__.lstrip("_"))
def test_the_check_finds(breach, tmp_path):
    bench = manifest.load()
    breach(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench), encoding="utf-8")
    os.symlink(manifest.ROOT / "benchmark", tmp_path / "benchmark")
    (tmp_path / "chip_smoke.py").write_text("")
    assert manifest.check(tmp_path) != []


def test_an_oversized_manifest_is_refused(tmp_path):
    bench = manifest.load()
    text = json.dumps(bench) + " " * (64 * 1024)
    (tmp_path / "BENCHMARK.json").write_text(text)
    os.symlink(manifest.ROOT / "benchmark", tmp_path / "benchmark")
    assert any("65536" in e for e in manifest.check(tmp_path))


def test_config_files_hold_only_plain_text(tmp_path):
    bench = manifest.load()
    for c in bench["configs"]:
        body = manifest.load_json(manifest.ROOT, c["file"])
        errors = []
        manifest._all_text(errors, c["file"], body)
        assert errors == []
