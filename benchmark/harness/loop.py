"""One process, one cell, one run: load, one first compute (the warm-up and
the full correctness check), the window, one last line, exit.

``main`` is what ``benchmark/run.py`` calls. It finds the cell's files by the
names ``BENCHMARK.json`` gives (configuration, traffic mix, the mix's query,
and in a traced run the readers of the cell's per-layer metrics), refuses to
measure off a TPU, and hands over to ``measure``, which the benchmark's tests
call at a tiny size on the CPU.

The loop is closed: one client, no think time, the next compute starts when
the last is checked and cleared away. Every compute builds a fresh expression
(new array names, so the program's structural cache is hit and not a stored
result) and, where it stores, a fresh target. The timed region of a compute
runs from the first call that builds the expression to the result in the
caller's hands; checking it, removing the last target and ``gc.collect()``
happen between computes, outside it. A compute that starts inside the window
runs to its end and is counted."""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass
from typing import Optional, Sequence

from . import manifest, trace_reduce
from .compile_log import CompileLog, between
from .spans import ANNOTATION_PREFIX, Recorder


class _StatsCapture:
    """A callback of the program's public kind: keeps the executor's counters
    of the compute it was given to."""

    stats: Optional[dict] = None

    def on_compute_end(self, event) -> None:
        self.stats = event.executor_stats


def check_device_path(stats: Optional[dict], rule: dict) -> None:
    """The configuration's ``device_path`` rule against a compute's counters:
    a fallback path must not pass for the device path."""
    if stats is None:
        raise AssertionError("the compute ended without executor_stats")
    nonzero = {k: stats[k] for k in rule.get("zero", []) if stats.get(k)}
    if nonzero:
        raise AssertionError(f"ops left the device path: {nonzero}")
    lacking = [k for k in rule.get("positive", []) if not stats.get(k)]
    if lacking:
        raise AssertionError(f"counters that must be positive are 0: {lacking}")


def check_mesh_shares(peaks: Sequence[int]) -> None:
    """Every chip held its share: no peak zero, none above twice the mean
    (a copy of ``chip_smoke.check_mesh_shares``)."""
    mean = sum(peaks) / len(peaks)
    if any(p == 0 or p > 2 * mean for p in peaks):
        raise AssertionError(
            f"per-device peak_bytes_in_use {list(peaks)}: a chip held "
            f"nothing, or more than twice the mean ({mean:.0f})"
        )


@dataclass
class Traced:
    """What a traced run hands to the readers of the per-layer metrics."""

    recorder: Recorder
    #: indices of the computes of the window (the run's first compute is -1)
    window: list
    #: the executor's counters of the last compute
    stats: dict
    first_compute_s: float
    #: {"programs", "compile_seconds", "cache_hits", "cache_misses"}
    compile_setup: dict
    compile_window: dict
    nominal_bytes: int
    chips: int
    device_kind: str
    bytes_limit: Optional[int]
    #: ``trace_reduce.reduce_trace``'s result, None where there is no trace
    device: Optional[dict] = None

    def busiest_per_compute(self, key: str) -> Optional[float]:
        """``device[key]`` of the busiest chip over the traced computes."""
        d = self.device
        return d[key][d["busiest"]] / d["computes"] if d else None

    def median_per_compute(self, seconds_of) -> Optional[float]:
        """Median over the window's computes of ``seconds_of(compute index)``;
        a compute for which it returns None is left out."""
        values = [v for v in map(seconds_of, self.window) if v is not None]
        return statistics.median(values) if values else None

    def span_seconds(self, name: str, self_time: bool = False) -> Optional[float]:
        """Median over the window's computes of the summed duration (or self
        time) of the spans called ``name``; None if there never was one."""
        rec = self.recorder
        if not any(s.name == name for s in rec.spans):
            return None
        measure = rec.self_seconds if self_time else (lambda s: s.seconds)
        return self.median_per_compute(
            lambda c: sum(measure(s) for s in rec.of(name, c))
        )


def _memory_peaks(devices) -> list:
    """``peak_bytes_in_use`` of each device; empty where the backend keeps no
    memory statistics (the CPU of the rehearsals)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return []
    return [int(s["peak_bytes_in_use"]) for s in stats]


def measure(*, root, bench, cell: dict, seed: int, seconds: float, trace: bool,
            devices, t_start: float) -> dict:
    """Run one cell on ``devices`` and return the object of the last line."""
    import jax

    import cubed_tpu as ct
    from cubed_tpu.parallel.mesh import make_mesh
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    entry = manifest.config_entry(bench, cell["config"])
    config = manifest.load_json(root, entry["file"])
    mix = manifest.load_json(root, manifest.traffic_file(cell["traffic"]))
    query = manifest.load_module(root, manifest.query_file(mix["query"]))
    if mix["loop"] != "closed" or mix["clients"] != 1:
        raise NotImplementedError(f"traffic {cell['traffic']}: only a closed loop of one client")
    deploy = config["deployment"]
    used = list(devices[: cell["chips"]])
    mesh = make_mesh(devices=used) if deploy["executor"].get("mesh") else None

    per_layer = manifest.metrics_for(bench, "per_layer", cell["name"]) if trace else []
    readers = {
        x["name"]: manifest.load_module(root, manifest.reader_file(x["name"]))
        for x in per_layer
    }
    recorder = Recorder() if trace else None
    for module in {id(mod): mod for mod in readers.values()}.values():
        for target, options in getattr(module, "SPANS", {}).items():
            recorder.wrap(target, **options)

    log = CompileLog()
    workdir = tempfile.mkdtemp(prefix="bench-")
    profiling = False
    try:
        spec = ct.Spec(work_dir=os.path.join(workdir, "work"),
                       allowed_mem=deploy["allowed_mem"])
        sources = query.make_sources(deploy, seed, workdir)

        def one(index: int):
            target = (
                os.path.join(workdir, f"target-{index}.zarr")
                if query.WRITES_TARGET else None
            )
            executor, cap = JaxExecutor(mesh=mesh), _StatsCapture()
            span = recorder.compute(index) if trace else contextlib.nullcontext()
            t0 = time.perf_counter()
            with span:
                expr = query.build(deploy, sources, spec, target)
                result = query.run(expr, executor, [cap], target)
            return result, time.perf_counter() - t0, cap.stats, target

        def check(result, stats, target, full: bool) -> None:
            check_device_path(stats, config.get("device_path", {}))
            query.check(deploy, sources, result, first_result, target, full)

        # the first compute: warm-up, compile or cache load, the full check
        mark0 = log.mark()
        first_result, first_compute_s, stats, target = one(-1)
        correct = True
        try:
            check(first_result, stats, target, full=True)
            peaks = _memory_peaks(used)
            if len(peaks) > 1:
                check_mesh_shares(peaks)
        except AssertionError:
            traceback.print_exc(file=sys.stdout)
            correct = False
        if target:
            shutil.rmtree(target, ignore_errors=True)
        gc.collect()
        mark1 = log.mark()
        print(f"first compute {first_compute_s:.3f} s; {between(mark0, mark1)}", flush=True)

        # the window
        times, attempted, failed = [], 0, 0
        kept = None  # (result, stats, target) of the newest compute that stores
        trace_dir = os.path.join(workdir, "trace")
        if trace and mix["profiled_computes"] > 0:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            profiling = True
        window_start = time.perf_counter()
        setup_s = window_start - t_start
        while time.perf_counter() - window_start < seconds:
            index = attempted
            attempted += 1
            try:
                result, dt, stats, target = one(index)
                times.append(dt)
                check(result, stats, target, full=False)
            except Exception:
                traceback.print_exc(file=sys.stdout)
                failed += 1
                result = stats = target = None
            if profiling and attempted >= mix["profiled_computes"]:
                jax.profiler.stop_trace()
                profiling = False
            if kept is not None and kept[2]:
                shutil.rmtree(kept[2], ignore_errors=True)
            kept = (result, stats, target)
            result = None
            gc.collect()
        window_s = time.perf_counter() - window_start
        mark2 = log.mark()
        if profiling:
            jax.profiler.stop_trace()
            profiling = False
        # the last target gets the full check the first one had
        if kept is not None and kept[2]:
            try:
                check(*kept, full=True)
            except AssertionError:
                traceback.print_exc(file=sys.stdout)
                failed += 1

        if not times:
            raise RuntimeError("no compute finished in the window")
        median = statistics.median(times)
        nominal = query.nominal_bytes(deploy)
        print(
            f"window {window_s:.3f} s: {attempted} computes, {failed} failed; "
            f"median {median:.6f} s mean {statistics.fmean(times):.6f} s "
            f"min {min(times):.6f} s max {max(times):.6f} s; nominal "
            f"{nominal / 1e9:.3f} GB a compute, {nominal / 1e9 / median:.3f} GB/s "
            f"at the median; in the window {between(mark1, mark2)}",
            flush=True,
        )
        peaks = _memory_peaks(used)
        print(f"peak_bytes_in_use per chip {peaks}; last compute's counters "
              f"{ {k: v for k, v in (stats or {}).items() if isinstance(v, int)} }",
              flush=True)

        device = {
            "platform": used[0].platform,
            "kind": used[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks, default=0),
        }
        out = {
            "correct": correct and failed == 0,
            "attempted": attempted,
            "failed": failed,
            "metrics": {},
            "device": device,
        }
        if not trace:
            values = {mix["metric"]: median, "setup_s": setup_s}
            for x in manifest.metrics_for(bench, "end_to_end", cell["name"]):
                out["metrics"][x["name"]] = {"value": values[x["name"]], "unit": x["unit"]}
            return out

        # the traced run: per-layer metrics from spans, counters and the trace
        limit = (used[0].memory_stats() or {}).get("bytes_limit")
        traced = Traced(
            recorder=recorder, window=list(range(attempted)), stats=stats or {},
            first_compute_s=first_compute_s,
            compile_setup=between(mark0, mark1),
            compile_window=between(mark1, mark2), nominal_bytes=nominal,
            chips=len(used), device_kind=used[0].device_kind, bytes_limit=limit,
        )
        if mix["profiled_computes"] > 0:
            # a trace that cannot be read costs the metrics that read it, not
            # the run: the spans and counters stand without it
            try:
                plain = trace_reduce.load_xplane(
                    trace_reduce.find_xplane(trace_dir), ANNOTATION_PREFIX
                )
                print("trace: " + trace_reduce.describe(plain), flush=True)
                traced.device = trace_reduce.reduce_trace(plain, ANNOTATION_PREFIX)
            except Exception:
                traceback.print_exc(file=sys.stdout)
            if traced.device is None:
                print("WARNING no device time could be read from the trace", flush=True)
        for x in per_layer:
            value = readers[x["name"]].read(traced)
            if value is not None:
                out["metrics"][x["name"]] = {"value": float(value), "unit": x["unit"]}
        if traced.device is not None:
            busy = traced.device["busy_s"]
            device["busy_s"] = sum(busy.get(d.id, 0.0) for d in used) / len(used)
            device["window_s"] = traced.device["window_s"]
            out["breakdown"] = {
                "device_ops": traced.device["device_ops"],
                "idle_gaps": traced.device["idle_gaps"],
            }
        return out
    finally:
        if profiling:
            jax.profiler.stop_trace()
        if recorder is not None:
            recorder.unwrap()
        log.close()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv: Optional[Sequence[str]], t_start: float) -> int:
    parser = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json once.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    root = manifest.ROOT
    bench = manifest.load(root)
    cell = manifest.cell(bench, args.workload)

    import jax

    devices = jax.devices()
    print(
        f"bench: workload={cell['name']} seed={args.seed} seconds={args.seconds:g} "
        f"trace={args.trace} platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} count={len(devices)} "
        f"compile_cache_dir={jax.config.jax_compilation_cache_dir}",
        flush=True,
    )
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"bench: {cell['name']} needs {cell['chips']} TPU chip(s); jax found "
            f"{len(devices)} device(s) of platform {devices[0].platform!r}. "
            "Nothing was measured.",
            file=sys.stderr,
        )
        return 1
    out = measure(
        root=root, bench=bench, cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, t_start=t_start,
    )
    print(json.dumps(out), flush=True)
    return 0
