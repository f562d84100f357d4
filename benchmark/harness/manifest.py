"""``BENCHMARK.json``: loading it, finding a cell's files by the names it
gives, and checking it against the contract before the driver does.

``check()`` applies every rule the driver states for the manifest (names,
units, free text, counts, bounds, the budget of a full check) and the
benchmark's own (every file a cell names exists and agrees with its entry).
It runs in the sandbox, costs no chip time, and is both a test
(``benchmark/tests/test_manifest.py``) and a script
(``python benchmark/check_manifest.py``)."""

from __future__ import annotations

import importlib.util
import json
import os
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = "benchmark"

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}\Z")
#: a key of ``reduced`` may never name a width
WIDTH = re.compile(
    r"(_dim|_rank)\Z|hidden|intermediate|latent|state_size|proj|head_size"
    r"|head_dim|expansion|experts_per_tok",
    re.IGNORECASE,
)
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads",
    "end_to_end", "per_layer",
}
#: a full check of the driver: runs, seconds allowed, with all 24 cells
MAX_CELLS = 24
CHECK_SECONDS = 43200


def load(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(root: Path, relative: str) -> dict:
    with open(Path(root) / relative) as f:
        return json.load(f)


def load_module(root: Path, relative: str):
    """Import one file of the benchmark by path (its name may hold dots)."""
    path = Path(root) / relative
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", relative), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r} in BENCHMARK.json "
        f"(it has {[w['name'] for w in manifest['workloads']]})"
    )


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def metrics_for(manifest: dict, group: str, cell_name: str) -> list:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that the
    cell reports: those with no ``workloads`` key, or that list it."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def traffic_file(traffic: str) -> str:
    return f"{BENCH_DIR}/traffic/{traffic}.json"


def query_file(query: str) -> str:
    return f"{BENCH_DIR}/queries/{query}.py"


def reader_file(metric: str) -> str:
    """A per-layer metric's reader: the part of its name before the first
    dot names the file, so that ``x.gen`` and ``x.zarr`` share ``x.py``."""
    return f"{BENCH_DIR}/layer_metrics/{metric.split('.', 1)[0]}.py"


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def _text(errors: list, what: str, value, limit: int = 200) -> None:
    """Free text: 1 to ``limit`` characters between 0x20 and 0x7E."""
    if not isinstance(value, str) or not 1 <= len(value) <= limit:
        errors.append(f"{what}: must be a string of 1 to {limit} characters")
    elif any(not 0x20 <= ord(ch) <= 0x7E for ch in value):
        bad = sorted({ch for ch in value if not 0x20 <= ord(ch) <= 0x7E})
        errors.append(f"{what}: characters outside 0x20-0x7E: {bad!r}")


def _name(errors: list, what: str, value) -> None:
    if not isinstance(value, str) or not NAME.match(value):
        errors.append(f"{what}: {value!r} is not a name ({NAME.pattern})")


def _keys(errors: list, what: str, entry: dict, required: set, optional=()) -> None:
    missing = required - set(entry)
    extra = set(entry) - required - set(optional)
    if missing:
        errors.append(f"{what}: lacks {sorted(missing)}")
    if extra:
        errors.append(f"{what}: has keys the contract does not know {sorted(extra)}")


def _under(path: str, paths: list) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/") for p in paths)


def _all_text(errors: list, what: str, value) -> None:
    """Every string anywhere inside a configuration or traffic file."""
    if isinstance(value, str):
        _text(errors, what, value)
    elif isinstance(value, dict):
        for k, v in value.items():
            _text(errors, f"{what} key", k)
            _all_text(errors, f"{what}.{k}", v)
    elif isinstance(value, list):
        for i, v in enumerate(value):
            _all_text(errors, f"{what}[{i}]", v)


def _check_own_files(errors: list, root: Path, m: dict) -> None:
    """The benchmark's own rules: every file that a cell or a metric names
    exists and agrees with its entry in the manifest."""
    cells, per_layer, configs = (
        m[k] if isinstance(m[k], list) else []
        for k in ("workloads", "per_layer", "configs")
    )
    config_names = [c.get("name") for c in configs]
    for w in cells:
        what = f"workload {w.get('name')!r}"
        tfile = traffic_file(str(w.get("traffic")))
        if not (root / tfile).is_file():
            errors.append(f"{what}: traffic file {tfile} does not exist")
            continue
        mix = load_json(root, tfile)
        _all_text(errors, tfile, mix)
        for key in ("query", "loop", "clients", "metric", "profiled_computes"):
            if key not in mix:
                errors.append(f"{tfile}: lacks {key!r}")
        if not (root / query_file(str(mix.get("query")))).is_file():
            errors.append(f"{tfile}: query file {query_file(str(mix.get('query')))} does not exist")
        mine = [x["name"] for x in metrics_for(m, "end_to_end", w.get("name"))]
        if mix.get("metric") not in mine or mix.get("metric") == "setup_s":
            errors.append(
                f"{what}: its mix reports {mix.get('metric')!r}, and the cell's "
                f"end-to-end metrics are {mine}"
            )
        others = [n for n in mine if n not in ("setup_s", mix.get("metric"))]
        if others:
            errors.append(f"{what}: nothing in its mix reports {others}")
        cfile = config_entry(m, w["config"]).get("file") if w.get("config") in config_names else None
        if cfile and (root / cfile).is_file():
            if load_json(root, cfile).get("chips") != w.get("chips"):
                errors.append(f"{what}: chips differs from the one in {cfile}")
    for x in per_layer:
        rfile = reader_file(str(x.get("name")))
        if not (root / rfile).is_file():
            errors.append(f"per_layer {x.get('name')!r}: reader {rfile} does not exist")
            continue
        declared = {d["name"]: d for d in load_module(root, rfile).METRICS}
        d = declared.get(x.get("name"))
        if d is None:
            errors.append(f"{rfile}: does not declare {x.get('name')!r}")
            continue
        for key in ("unit", "better", "source", "layer", "moves"):
            if d.get(key) != x.get(key):
                errors.append(
                    f"per_layer {x.get('name')!r}: {key} {x.get(key)!r} differs "
                    f"from {d.get(key)!r} in {rfile}"
                )


def check(root: Path = ROOT) -> list:
    """Every breach of the contract found, as one line each; empty if none."""
    root = Path(root)
    errors: list = []
    raw = (root / "BENCHMARK.json").read_bytes()
    if len(raw) > 64 * 1024:
        errors.append(f"BENCHMARK.json: {len(raw)} bytes, at most 65536")
    m = json.loads(raw)
    if set(m) != TOP_KEYS:
        errors.append(f"top level: keys {sorted(m)}, expected {sorted(TOP_KEYS)}")
        return errors

    # paths and command
    paths = m["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        errors.append("paths: 1 to 16 directories")
        return errors
    for p in paths:
        if (
            not isinstance(p, str) or not PATH.match(p) or p.startswith("/")
            or ".." in p.split("/")
        ):
            errors.append(f"paths: {p!r} is not a relative path inside the repo")
        elif not (root / p).is_dir():
            errors.append(f"paths: {p!r} is not a directory")
    command = m["command"]
    if not isinstance(command, list) or not 1 <= len(command) <= 32:
        errors.append("command: a list of 1 to 32 strings")
    else:
        for word in command:
            _text(errors, f"command word {word!r}", word)
            if not isinstance(word, str):
                continue
            if word.startswith("/") or ".." in word.split("/"):
                errors.append(f"command: {word!r} leads out of the repo")
            elif (root / word).exists() and not _under(word, paths):
                errors.append(f"command: {word!r} is a file outside paths")

    # run_seconds and the budget of a full check with every cell there may be
    rs = m["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 10 <= rs <= 51:
        errors.append(f"run_seconds: {rs!r} is not a whole number from 10 to 51")
    else:
        need = (2 + 14 * MAX_CELLS) * (rs + 60) + MAX_CELLS * 2 * 90 + 1200
        if need > CHECK_SECONDS:
            errors.append(
                f"run_seconds: a full check of {MAX_CELLS} cells needs {need} s, "
                f"over {CHECK_SECONDS}"
            )

    # files under paths are named from the characters of a name and "/"
    for p in paths:
        for dirpath, dirnames, filenames in os.walk(root / p):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                if not fn.endswith(".pyc") and not PATH.match(rel):
                    errors.append(f"file {rel!r}: characters outside A-Za-z0-9_.-/")

    # configurations
    configs = m["configs"]
    if not isinstance(configs, list) or not 1 <= len(configs) <= 24:
        errors.append("configs: 1 to 24 entries")
        configs = []
    seen_files = set()
    for c in configs:
        what = f"config {c.get('name')!r}"
        _keys(errors, what, c, {"name", "source", "file", "reduced", "why"})
        _name(errors, f"{what} name", c.get("name"))
        _text(errors, f"{what} source", c.get("source"))
        _text(errors, f"{what} why", c.get("why"))
        file = c.get("file", "")
        if not isinstance(file, str) or not _under(file, paths):
            errors.append(f"{what}: file {file!r} is not under paths")
        elif not (root / file).is_file():
            errors.append(f"{what}: file {file!r} does not exist")
        if file in seen_files:
            errors.append(f"{what}: file {file!r} is another configuration's too")
        seen_files.add(file)
        reduced = c.get("reduced")
        if not isinstance(reduced, list) or len(reduced) > 16:
            errors.append(f"{what}: reduced is a list of at most 16 keys")
            reduced = []
        for key in reduced:
            _name(errors, f"{what} reduced key", key)
            if isinstance(key, str) and WIDTH.search(key):
                errors.append(f"{what}: reduced names a width, {key!r}")
        if isinstance(file, str) and (root / file).is_file() and _under(file, paths):
            body = load_json(root, file)
            _all_text(errors, f"{file}", body)
            if body.get("source") != c.get("source"):
                errors.append(f"{what}: source differs from the one in {file}")
            if sorted(body.get("reduced", {})) != sorted(reduced):
                errors.append(
                    f"{what}: reduced {sorted(reduced)} differs from the keys "
                    f"of reduced in {file} {sorted(body.get('reduced', {}))}"
                )
            for key in ("deployment", "guarantees", "assumed", "chips"):
                if key not in body:
                    errors.append(f"{file}: lacks {key!r}")
    config_names = [c.get("name") for c in configs]

    # end-to-end metrics
    e2e = m["end_to_end"]
    if not isinstance(e2e, list) or not 1 <= len(e2e) <= 16:
        errors.append("end_to_end: 1 to 16 metrics")
        e2e = []
    for x in e2e:
        what = f"end_to_end {x.get('name')!r}"
        _keys(errors, what, x, {"name", "unit", "better", "bound", "source"},
              optional=("workloads",))
        if x.get("source") not in ("host_clock", "device_trace"):
            errors.append(f"{what}: source must be host_clock or device_trace")
        bound = x.get("bound")
        if not isinstance(bound, (int, float)) or not 0.01 <= bound <= 0.25:
            errors.append(f"{what}: bound {bound!r} is not between 0.01 and 0.25")
    if "setup_s" not in [x.get("name") for x in e2e]:
        errors.append("end_to_end: setup_s is missing")
    for x in e2e:
        if x.get("name") == "setup_s" and "workloads" in x:
            errors.append("end_to_end 'setup_s': every cell reports it, no workloads key")

    # per-layer metrics
    per_layer = m["per_layer"]
    if not isinstance(per_layer, list) or not 1 <= len(per_layer) <= 128:
        errors.append("per_layer: 1 to 128 metrics")
        per_layer = []
    for x in per_layer:
        what = f"per_layer {x.get('name')!r}"
        _keys(errors, what, x,
              {"name", "unit", "better", "source", "layer", "moves"},
              optional=("workloads",))
        if x.get("source") not in SOURCES:
            errors.append(f"{what}: source must be one of {SOURCES}")
        _text(errors, f"{what} layer", x.get("layer"))

    # what all metrics share
    metric_names = [x.get("name") for x in e2e + per_layer]
    for x in e2e + per_layer:
        what = f"metric {x.get('name')!r}"
        _name(errors, f"{what} name", x.get("name"))
        if not isinstance(x.get("unit"), str) or not UNIT.match(x["unit"]):
            errors.append(f"{what}: unit {x.get('unit')!r} ({UNIT.pattern})")
        if x.get("better") not in ("lower", "higher"):
            errors.append(f"{what}: better must be lower or higher")
        name = x.get("name") or ""
        if (name.endswith("_roofline") or "mfu" in name) and x.get("unit") != "%":
            errors.append(f"{what}: a roofline or mfu share has the unit %")
    for n in set(metric_names):
        if metric_names.count(n) > 1:
            errors.append(f"metric {n!r}: the name appears twice")
    for n in set(config_names):
        if config_names.count(n) > 1:
            errors.append(f"config {n!r}: the name appears twice")

    # cells
    cells = m["workloads"]
    if not isinstance(cells, list) or not 1 <= len(cells) <= MAX_CELLS:
        errors.append(f"workloads: 1 to {MAX_CELLS} cells")
        cells = []
    cell_names = [w.get("name") for w in cells]
    pairs = set()
    for w in cells:
        what = f"workload {w.get('name')!r}"
        _keys(errors, what, w, {"name", "config", "traffic", "chips", "why"})
        for key in ("name", "config", "traffic"):
            _name(errors, f"{what} {key}", w.get(key))
        _text(errors, f"{what} why", w.get("why"))
        if w.get("chips") not in (1, 4):
            errors.append(f"{what}: chips must be 1 or 4")
        if w.get("config") not in config_names:
            errors.append(f"{what}: config {w.get('config')!r} is not defined")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errors.append(f"{what}: the pair {pair} appears twice")
        pairs.add(pair)
        if cell_names.count(w.get("name")) > 1:
            errors.append(f"{what}: the name appears twice")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        errors.append(f"workloads: {four} of {len(cells)} cells ask for 4 chips")
    for c in config_names:
        if c not in [w.get("config") for w in cells]:
            errors.append(f"config {c!r}: no cell uses it")

    # metrics against cells
    for x in e2e + per_layer:
        for w in x.get("workloads", []):
            if w not in cell_names:
                errors.append(f"metric {x.get('name')!r}: lists unknown cell {w!r}")
        if "workloads" in x and not x["workloads"]:
            errors.append(f"metric {x.get('name')!r}: an empty workloads list")
    e2e_by_name = {x.get("name"): x for x in e2e}
    for x in per_layer:
        moved = e2e_by_name.get(x.get("moves"))
        if moved is None:
            errors.append(
                f"per_layer {x.get('name')!r}: moves {x.get('moves')!r}, "
                "which is no end-to-end metric"
            )
            continue
        for w in x.get("workloads", cell_names):
            if "workloads" in moved and w not in moved["workloads"]:
                errors.append(
                    f"per_layer {x.get('name')!r}: cell {w!r} does not report "
                    f"{moved['name']!r}, the metric it moves"
                )
    for w in cells:
        name = w.get("name")
        mine = [x["name"] for x in metrics_for(m, "end_to_end", name)]
        if "setup_s" not in mine or len(mine) < 2:
            errors.append(f"workload {name!r}: reports {mine}, needs setup_s and one more")
        if not metrics_for(m, "per_layer", name):
            errors.append(f"workload {name!r}: reports no per-layer metric")

    _check_own_files(errors, root, m)
    return errors
