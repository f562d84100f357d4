"""Benchmark: ALL FIVE BASELINE.json configs (+ a scaled addsum), measured
every run.

1. ``addsum`` — config #1: ``xp.add(a, b).sum()`` on 5000x5000 f64 at
   (1000, 1000) chunks.
2. ``matmul`` — config #4: ``sum(a @ b)`` on 4000x4000 at (1000, 1000)
   chunks — the blockwise contraction + tree-reduce path, reported in
   GFLOP/s (the MXU configuration).
3. ``elemwise`` — config #2: a fused unary+binary elementwise chain
   ``sum(sqrt(|sin(a)*b + cos(b)|))`` on 6000x6000.
4. ``reduce`` — config #3: 2-level axis reduction ``max(mean(a, axis=0))``
   on 8000x8000 via the reduction tree.
5. ``vorticity`` — config #5: the pangeo-vorticity pipeline (reference
   examples/pangeo-vorticity.ipynb): four random arrays,
   ``mean(a[1:]*x + b[1:]*y)`` at (500, 450, 400) f64, chunks=100 (the
   notebook's (1000,900,800) exceeds one chip's HBM; the driver's mesh
   dryrun covers the sharded path).

A sixth metric line, ``addsum_scaled`` (16000x16000), keeps config #1
informative at a size where device work, not dispatch, dominates.

The parent process never imports jax: each device phase runs in a
subprocess with its own timeout and owns the chip in turn (an accelerator
belongs to one process at a time). A device phase refuses to run unless
``jax.devices()[0].platform`` is ``tpu`` — there is no CPU fallback — and
the run exits non-zero if any device phase failed. Every emitted line names
the device it was measured on.

- The numpy baselines (reference's single-process PythonDagExecutor
  semantics) are measured once and recorded in ``BASELINE_RECORDED.json``
  (committed); they are only re-measured if the record is absent.
- The host-only fleet sweeps further down run on the CPU platform by
  construction (``JAX_PLATFORMS=cpu``): they time the control plane, not
  the device, and are never reported under a device metric's name.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RECORD_PATH = os.path.join(REPO, "BASELINE_RECORDED.json")

OVERALL_DEADLINE_S = 540  # print the JSON lines well inside 10 minutes
BASELINE_TIMEOUT_S = 240

SHAPE = (500, 450, 400)
CHUNK = 100
_elems = SHAPE[0] * SHAPE[1] * SHAPE[2]
#: bytes flowing through the pipeline: 4 generated arrays + 2 sliced reads
WORK_BYTES = 6 * _elems * 8

#: BASELINE.json config #1: xp.add(a, b).sum() on 5000x5000 f64 @ (1000,1000)
ADDSUM_SHAPE = (5000, 5000)
ADDSUM_CHUNK = 1000
#: 2 generated arrays + 1 fused add+sum pass over both
ADDSUM_WORK_BYTES = 2 * ADDSUM_SHAPE[0] * ADDSUM_SHAPE[1] * 8

#: scaled addsum variant: the canonical 400 MB config is small enough that
#: per-dispatch latency can hide framework changes; 16000x16000 (4.1 GB
#: through the pipe) keeps the same op shape at 10x the volume
ADDSUM_SCALED_SHAPE = (16000, 16000)
ADDSUM_SCALED_CHUNK = 2000
ADDSUM_SCALED_WORK_BYTES = 2 * ADDSUM_SCALED_SHAPE[0] * ADDSUM_SCALED_SHAPE[1] * 8

#: BASELINE.json config #4: matmul/tensordot via blockwise contraction.
#: sum(a @ b) keeps the output on-device (a scalar fetch, not a 128MB
#: transfer), so the number measures the contraction, not the transfer.
MATMUL_N = 4000
MATMUL_CHUNK = 1000
MATMUL_FLOPS = 2 * MATMUL_N**3

#: BASELINE.json config #2: unary+binary elementwise chain (the Array-API
#: elementwise suite shape): sum(sqrt(|sin(a)*b + cos(b)|)) — 2 generated
#: arrays, 6 elementwise ops fused into one pass, then a tree-reduce.
ELEMWISE_SHAPE = (6000, 6000)
ELEMWISE_CHUNK = 1000
ELEMWISE_WORK_BYTES = 2 * ELEMWISE_SHAPE[0] * ELEMWISE_SHAPE[1] * 8

#: BASELINE.json config #3: axis reductions via core.ops.reduction
#: tree-reduce: max(mean(a, axis=0)) — a 2-level reduction over both axes.
REDUCE_SHAPE = (8000, 8000)
REDUCE_CHUNK = 1000
REDUCE_WORK_BYTES = REDUCE_SHAPE[0] * REDUCE_SHAPE[1] * 8

_T0 = time.monotonic()


def _remaining(cap: float) -> float:
    return max(10.0, min(cap, OVERALL_DEADLINE_S - (time.monotonic() - _T0)))


WORKLOAD = r"""
import json, sys, tempfile, time
sys.path.insert(0, {repo!r})
import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.random

spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="4GB")
workload = {workload!r}
executor = None
device = None
if {use_jax_executor!r}:
    # a device phase measures the chip or nothing: no CPU fallback
    import jax
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        sys.exit("device phase needs a TPU; jax found platform=%r" % d0.platform)
    device = dict(platform=d0.platform, kind=d0.device_kind,
                  count=len(jax.devices()))
    from cubed_tpu.runtime.executors.jax import JaxExecutor
    if workload == "matmul_bf16":
        # the MXU opt-in: f32 storage/elementwise, one-pass bf16 contractions
        executor = JaxExecutor(
            compute_dtype="float32", matmul_precision="bfloat16"
        )
    elif workload == "vorticity_f32":
        # f32 ingestion for the f64 pipeline (v5e has no native f64)
        executor = JaxExecutor(compute_dtype="float32")
    else:
        executor = JaxExecutor()

def build():
    if workload in ("addsum", "addsum_scaled"):
        if workload == "addsum":
            shape, chunk = {addsum_shape!r}, {addsum_chunk!r}
        else:
            shape, chunk = {addsum_scaled_shape!r}, {addsum_scaled_chunk!r}
        a = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
        b = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
        return xp.sum(xp.add(a, b))
    if workload in ("matmul", "matmul_bf16"):
        n, chunk = {matmul_n!r}, {matmul_chunk!r}
        a = cubed_tpu.random.random((n, n), chunks=chunk, spec=spec)
        b = cubed_tpu.random.random((n, n), chunks=chunk, spec=spec)
        return xp.sum(xp.matmul(a, b))
    if workload == "elemwise":
        shape, chunk = {elemwise_shape!r}, {elemwise_chunk!r}
        a = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
        b = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
        return xp.sum(
            xp.sqrt(xp.abs(xp.add(xp.multiply(xp.sin(a), b), xp.cos(b))))
        )
    if workload == "reduce":
        shape, chunk = {reduce_shape!r}, {reduce_chunk!r}
        a = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
        return xp.max(xp.mean(a, axis=0))
    shape, chunk = {shape!r}, {chunk!r}
    a = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
    b = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
    x = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
    y = cubed_tpu.random.random(shape, chunks=chunk, spec=spec)
    return xp.mean(xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:])))

kw = dict(executor=executor) if executor is not None else {{}}
if {warmup!r}:
    # compile warmup (persistent cache + in-process caches)
    w0 = time.perf_counter()
    build().compute(**kw)
    print("warmup done in", round(time.perf_counter() - w0, 2), "s",
          file=sys.stderr, flush=True)

# capture the per-compute observability snapshot (task counters, IO bytes,
# per-op wall clock) so bench records carry metric trajectories for free
class _StatsCapture:
    stats = None
    def on_compute_end(self, event):
        self.stats = event.executor_stats

cap = _StatsCapture()
s = build()
t0 = time.perf_counter()
val = s.compute(callbacks=[cap], **kw)
t1 = time.perf_counter()
v = float(val)
if workload in ("addsum", "addsum_scaled"):
    sh = {addsum_shape!r} if workload == "addsum" else {addsum_scaled_shape!r}
    n = sh[0] * sh[1]
    assert 0.95 < v / n < 1.05, v  # sum of u1+u2 has mean 1.0 per element
elif workload in ("matmul", "matmul_bf16"):
    n = {matmul_n!r}
    # E[sum(A@B)] = n^3/4 for uniforms; bf16 input rounding widens the window
    lo, hi = (0.85, 1.15) if workload == "matmul_bf16" else (0.9, 1.1)
    assert lo < v / (0.25 * n**3) < hi, v
elif workload == "elemwise":
    n = {elemwise_shape!r}[0] * {elemwise_shape!r}[1]
    assert 0.5 < v / n < 1.1, v  # E[sqrt(|sin(u)v + cos(v)|)] is O(1)
elif workload == "reduce":
    assert 0.45 < v < 0.55, v  # max over 8000 column means of uniforms ~ 0.5
else:
    assert 0.45 < v < 0.55, v  # mean of u1*u2 + u3*u4 over uniforms is ~0.5
print(json.dumps(
    {{"elapsed": t1 - t0, "value": v, "device": device,
      "executor_stats": cap.stats}},
    default=str,
), flush=True)
"""

#: fleet sizes for the scaling sweep (tasks/sec per size; efficiency is
#: tps(n) / (n * tps(1))). 16/32 are production-ish fleet sizes: the
#: ROADMAP item-5 target is that scaling efficiency there is a TRACKED,
#: gated number, not an anecdote — worker processes are sleep-bound, so a
#: 2-core container can still host 32 of them meaningfully
FLEET_SIZES = (1, 2, 4, 8, 16, 32)
#: tasks in the sweep workload and the per-task sleep: sleep-bound bodies
#: make tasks/sec measure the FLEET's dispatch/requeue machinery (what the
#: autoscaler and drain path touch), not this host's core count. 128
#: tasks keep the largest fleet at 4 tasks/worker so the number still
#: measures sustained dispatch, not a one-round burst
FLEET_TASKS = 128
FLEET_TASK_DELAY_S = 0.05

FLEET_SCALING = r"""
import json, sys, tempfile, threading, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor


class SleepAdd:
    def __init__(self, delay_s):
        self.delay_s = delay_s

    def __call__(self, x):
        time.sleep(self.delay_s)
        return x + 1.0


an = np.arange({tasks!r} * 4, dtype=np.float64).reshape(-1, 4)
out = {{}}
reg = get_registry()
for n in {sizes!r}:
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB")
    a = ct.from_array(an, chunks=(1, 4), spec=spec)  # one row per task
    r = ct.map_blocks(SleepAdd({delay!r}), a, dtype=np.float64)
    ex = DistributedDagExecutor(n_local_workers=n)
    # the dispatch_utilization gauge is live only while the dispatch loop
    # runs (the loop zeroes it on exit), so sample it from the side during
    # the compute; overhead/frame numbers are counter deltas (full
    # snapshot(), not snapshot_delta — gauges never survive the delta)
    before = reg.snapshot()
    util_samples = []
    stop = threading.Event()

    def sample(samples=util_samples, ev=stop):
        while not ev.wait(0.2):
            u = reg.snapshot().get("dispatch_utilization")
            if u:
                samples.append(u)

    try:
        ex._ensure_fleet()  # boot outside the timed window
        threading.Thread(target=sample, daemon=True).start()
        t0 = time.perf_counter()
        val = np.asarray(r.compute(executor=ex))
        elapsed = time.perf_counter() - t0
    finally:
        stop.set()
        ex.close()
    assert (val == an + 1.0).all()
    after = reg.snapshot()
    delta = lambda k: (after.get(k) or 0) - (before.get(k) or 0)
    out[str(n)] = {{
        "tasks_per_s": {tasks!r} / elapsed,
        # peak windowed utilization: the saturation signal ("pegged at
        # ~1.0 while queue_depth grows" is what the alert fires on)
        "dispatch_utilization": (
            max(util_samples) if util_samples else None
        ),
        "dispatch_overhead_ms": delta("dispatch_submit_s")
        / {tasks!r} * 1000.0,
        "coord_frames_sent": delta("coord_frames_sent"),
    }}
    print("fleet", n, "workers:",
          round(out[str(n)]["tasks_per_s"], 1), "tasks/s,",
          "dispatch", round(out[str(n)]["dispatch_overhead_ms"], 3),
          "ms/task, util", out[str(n)]["dispatch_utilization"],
          file=sys.stderr, flush=True)
print(json.dumps(out), flush=True)
"""


#: deep-chain critical-path config (pangeo-vorticity-style depth without
#: its volume): DEPTH non-fusable map_blocks steps over an NxN grid of
#: CHUNKxCHUNK blocks, with a ROTATING straggler — at depth d, block
#: (d mod nblocks) sleeps DELAY. Under the op-level scheduler every op
#: waits for its own straggler (wall ≈ DEPTH x DELAY); under the dataflow
#: scheduler the straggler chains are independent 1:1 chunk chains, so
#: wall ≈ DELAY + work. The ratio is the number the barrier kill is on
#: the hook for.
SCHED_DEPTH = 6
SCHED_N = 8
SCHED_CHUNK = 2
SCHED_DELAY_S = 0.4

SCHEDULER_OVERLAP = r"""
import json, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor

DEPTH, N, CHUNK, DELAY = {depth!r}, {n!r}, {chunk!r}, {delay!r}
NBR = N // CHUNK


class StragglerStep:
    def __init__(self, depth):
        self.depth = depth

    def __call__(self, x, block_id=None):
        if block_id[0] * NBR + block_id[1] == self.depth % (NBR * NBR):
            time.sleep(DELAY)
        return x + 1.0


an = np.arange(N * N, dtype=np.float64).reshape(N, N)
out = {{}}
for mode in ("oplevel", "dataflow"):
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB",
                   scheduler=mode)
    a = ct.from_array(an, chunks=(CHUNK, CHUNK), spec=spec)
    r = a
    for d in range(DEPTH):
        r = ct.map_blocks(StragglerStep(d), r, dtype=np.float64)
    reg = get_registry()
    before = reg.snapshot()
    t0 = time.perf_counter()
    # optimize_graph=False keeps the chain DEEP (fusion would collapse a
    # pure elementwise chain into one op and hide the barrier question)
    val = np.asarray(r.compute(executor=AsyncPythonDagExecutor(),
                               optimize_graph=False))
    elapsed = time.perf_counter() - t0
    delta = reg.snapshot_delta(before)
    assert (val == an + DEPTH).all()
    out[mode] = {{
        "elapsed": elapsed,
        "tasks_dispatched_early": delta.get("tasks_dispatched_early", 0),
        "op_barrier_waits": delta.get("op_barrier_waits", 0),
    }}
    print("scheduler", mode, round(elapsed, 2), "s",
          file=sys.stderr, flush=True)
out["speedup"] = out["oplevel"]["elapsed"] / max(
    out["dataflow"]["elapsed"], 1e-9
)
print(json.dumps(out), flush=True)
"""


def measure_scheduler_overlap(timeout: float):
    """Deep-chain critical path: op-level vs dataflow wall clock.

    Runs on the host only (threaded executor, CPU platform). Returns
    ``{"oplevel": {...}, "dataflow": {...}, "speedup": x}`` or None on
    failure — additive, never the reason a bench run dies."""
    script = SCHEDULER_OVERLAP.format(
        repo=REPO, depth=SCHED_DEPTH, n=SCHED_N, chunk=SCHED_CHUNK,
        delay=SCHED_DELAY_S,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"scheduler overlap failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:
        print(f"scheduler overlap sweep skipped: {e}", file=sys.stderr)
        return None


def measure_fleet_scaling(timeout: float):
    """tasks/sec on the distributed fleet at 1→2→4→8→16→32 local workers.

    Runs on the host only (the fleet path never touches a device); each size
    boots a fresh fleet, runs a sleep-bound ``FLEET_TASKS``-task compute,
    and reports tasks/sec. The parent derives per-size scaling efficiency
    (``tps(n) / (n * tps(1))``) so fleet-dispatch regressions become a
    tracked number instead of an anecdote — and, per size, the
    control-plane story behind the curve: peak ``dispatch_utilization``,
    mean per-task ``dispatch_overhead_ms`` and coordinator frames sent,
    so "the coordinator saturates" is a recorded trajectory, not a
    profiling session. Returns ``None`` on failure — the scaling record
    is additive, never the reason a bench run dies."""
    script = FLEET_SCALING.format(
        repo=REPO, sizes=list(FLEET_SIZES), tasks=FLEET_TASKS,
        delay=FLEET_TASK_DELAY_S,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"fleet scaling failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        rows = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:
        print(f"fleet scaling sweep skipped: {e}", file=sys.stderr)
        return None
    tps = {size: row["tasks_per_s"] for size, row in rows.items()}
    dispatch = {
        size: {
            k: row.get(k)
            for k in (
                "dispatch_utilization", "dispatch_overhead_ms",
                "coord_frames_sent",
            )
        }
        for size, row in rows.items()
    }
    base = tps.get("1")
    efficiency = {
        size: tp / (int(size) * base)
        for size, tp in tps.items()
        if base and int(size) > 1
    }
    return {
        "tasks_per_s": tps, "efficiency": efficiency, "dispatch": dispatch,
    }


#: coordinator-recovery workload: enough sleep-bound tasks that the kill
#: reliably lands mid-compute, small enough to keep the 3-phase sweep
#: (uninterrupted / killed-at-50% / resume) under ~30s of compute
RECOVERY_TASKS = 36
RECOVERY_TASK_DELAY_S = 0.12

COORD_RECOVERY = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor

mode = sys.argv[1]


def sleep_add(x):
    time.sleep({delay!r})
    return x + 1.0


spec = ct.Spec(work_dir={work_dir!r}, allowed_mem="2GB",
               journal={journal!r})
an = np.arange({tasks!r} * 4, dtype=np.float64).reshape(-1, 4)
a = ct.from_array(an, chunks=(1, 4), spec=spec)  # one row per task
r = ct.map_blocks(sleep_add, a, dtype=np.float64)
total = r.plan.num_tasks()

ex = DistributedDagExecutor(n_local_workers=2)
try:
    ex._ensure_fleet()  # boot outside the timed window
    reg = get_registry()
    before = reg.snapshot()
    t0 = time.perf_counter()
    if mode == "resume":
        val = ex.resume_compute(r, {journal!r})
    else:
        val = np.asarray(r.compute(executor=ex))
    elapsed = time.perf_counter() - t0
    delta = reg.snapshot_delta(before)
    assert (val == an + 1.0).all()
    print(json.dumps({{
        "elapsed": elapsed, "total": total,
        "tasks_skipped_resume": delta.get("tasks_skipped_resume", 0),
        "resumed_tasks": delta.get("tasks_completed", 0),
    }}), flush=True)
finally:
    ex.close()
"""


def measure_coordinator_recovery(timeout: float):
    """Kill-the-coordinator-at-50%-then-resume vs an uninterrupted run.

    Three phases over the same plan (deterministic op names via a pinned
    CUBED_TPU_CONTEXT_ID): (1) uninterrupted with the journal armed — the
    baseline, journal overhead included; (2) the same compute SIGKILLed
    when the fsync'd journal shows ~50% of tasks complete; (3)
    ``resume_compute`` from the journal in a fresh process. ``elapsed`` is
    the total recovery wall clock (run-to-kill + resume), so the generic
    perf gate flags a >20% regression like any other config. Returns None
    on failure — additive, never the reason a bench run dies."""
    import shutil
    import signal
    import tempfile

    deadline = time.monotonic() + timeout
    work_dir = tempfile.mkdtemp()
    journal = os.path.join(work_dir, "bench.journal.jsonl")
    script = COORD_RECOVERY.format(
        repo=REPO, work_dir=work_dir, journal=journal,
        tasks=RECOVERY_TASKS, delay=RECOVERY_TASK_DELAY_S,
    )
    env = dict(_cpu_env(), CUBED_TPU_CONTEXT_ID="cubed-benchrec")
    try:
        from cubed_tpu.runtime.journal import load_journal

        # phase 1: uninterrupted baseline (journal on, like the real run)
        out = subprocess.run(
            [sys.executable, "-c", script, "full"], env=env,
            capture_output=True, text=True,
            timeout=max(10.0, deadline - time.monotonic()),
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"uninterrupted run failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        full = json.loads(out.stdout.strip().splitlines()[-1])
        os.unlink(journal)  # phase 2 writes a fresh journal

        # phase 2: the same compute, coordinator hard-killed at ~50%.
        # Its own session/process group, so the kill takes the client AND
        # its local worker subprocesses — orphaned workers would otherwise
        # burn CPU (and hammer the dead port) throughout the timed resume
        proc = subprocess.Popen(
            [sys.executable, "-c", script, "run"], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            start_new_session=True,
        )
        t0 = time.perf_counter()
        killed = False
        try:
            while time.monotonic() < deadline and proc.poll() is None:
                if os.path.exists(journal) and len(
                    load_journal(journal)["completed"]
                ) >= RECOVERY_TASKS // 2 + 1:
                    os.killpg(proc.pid, signal.SIGKILL)
                    killed = True
                    break
                time.sleep(0.05)
            run_to_kill = time.perf_counter() - t0
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait(timeout=30)
        if not killed:
            raise RuntimeError("compute finished before the kill landed")

        # phase 3: resume from the journal in a fresh process
        out = subprocess.run(
            [sys.executable, "-c", script, "resume"], env=env,
            capture_output=True, text=True,
            timeout=max(10.0, deadline - time.monotonic()),
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"resume failed (rc={out.returncode}): {out.stderr[-2000:]}"
            )
        resume = json.loads(out.stdout.strip().splitlines()[-1])
        recovery_total = run_to_kill + resume["elapsed"]
        rec = {
            # the gated number: kill-at-50% + resume, end to end
            "elapsed": recovery_total,
            "uninterrupted_s": full["elapsed"],
            "interrupted_run_s": run_to_kill,
            "resume_s": resume["elapsed"],
            "recovery_overhead_x": (
                recovery_total / full["elapsed"] if full["elapsed"] else None
            ),
            "tasks_skipped_resume": resume["tasks_skipped_resume"],
            "resumed_tasks": resume["resumed_tasks"],
            "total_tasks": resume["total"],
        }
        print(
            f"coordinator recovery: uninterrupted {full['elapsed']:.2f}s, "
            f"kill@50%+resume {recovery_total:.2f}s "
            f"({resume['tasks_skipped_resume']} task(s) skipped on resume)",
            file=sys.stderr, flush=True,
        )
        return rec
    except Exception as e:
        print(f"coordinator recovery sweep skipped: {e}", file=sys.stderr)
        return None
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


COORD_FAILOVER = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor

mode = sys.argv[1]


def sleep_add(x):
    time.sleep({delay!r})
    return x + 1.0


spec = ct.Spec(work_dir={work_dir!r}, allowed_mem="2GB",
               journal={journal!r})
an = np.arange({tasks!r} * 4, dtype=np.float64).reshape(-1, 4)
a = ct.from_array(an, chunks=(1, 4), spec=spec)  # one row per task
r = ct.map_blocks(sleep_add, a, dtype=np.float64)
total = r.plan.num_tasks()

if mode == "adopt":
    # the successor: no workers of its own — it adopts the orphaned
    # fleet the killed coordinator left running
    ex = DistributedDagExecutor(
        n_local_workers=0, worker_threads=1,
        control_dir={control_dir!r}, worker_start_timeout=60.0,
    )
else:
    ex = DistributedDagExecutor(
        n_local_workers=2, worker_threads=1, control_dir={control_dir!r},
    )
try:
    reg = get_registry()
    before = reg.snapshot()
    t0 = time.perf_counter()
    if mode == "adopt":
        val = ex.resume_compute(r, {journal!r})
    else:
        ex._ensure_fleet()  # boot outside the timed window (full mode)
        t0 = time.perf_counter()
        val = np.asarray(r.compute(executor=ex))
    elapsed = time.perf_counter() - t0
    delta = reg.snapshot_delta(before)
    assert (np.asarray(val) == an + 1.0).all()
    print(json.dumps({{
        "elapsed": elapsed, "total": total,
        "takeovers": ex.stats.get("coordinator_takeovers", 0),
        "readopted": ex.stats.get("tasks_readopted", 0),
        "workers_lost": ex.stats.get("workers_lost", 0),
        "tasks_skipped_resume": delta.get("tasks_skipped_resume", 0),
        "resumed_tasks": delta.get("tasks_completed", 0),
    }}), flush=True)
finally:
    ex.close()
"""


def measure_coordinator_failover(timeout: float):
    """Live takeover vs an uninterrupted run: SIGKILL the coordinator
    PROCESS at ~50% (its local worker subprocesses survive as orphans),
    then a successor pointed at the same control_dir adopts the live
    fleet and finishes the compute.

    ``elapsed`` is the total failover wall clock (run-to-kill + the
    successor's adopt-and-finish), gated >20% like any other config;
    ``failover_overhead_x`` is the ratio against the uninterrupted
    baseline (the acceptance bound is < 2x). Returns None on failure —
    additive, never the reason a bench run dies."""
    import shutil
    import signal
    import tempfile

    deadline = time.monotonic() + timeout
    work_dir = tempfile.mkdtemp()
    journal = os.path.join(work_dir, "bench.journal.jsonl")
    control_dir = os.path.join(work_dir, "ctrl")
    script = COORD_FAILOVER.format(
        repo=REPO, work_dir=work_dir, journal=journal,
        control_dir=control_dir,
        tasks=RECOVERY_TASKS, delay=RECOVERY_TASK_DELAY_S,
    )
    env = dict(_cpu_env(), CUBED_TPU_CONTEXT_ID="cubed-benchfo")

    def _reap_fleet():
        # kill any orphaned worker processes the control log records (a
        # failed takeover must not leak fleet processes into later sweeps)
        from cubed_tpu.runtime.journal import control_log_path, load_control

        try:
            prior = load_control(control_log_path(control_dir))
        except Exception:
            return
        for wrec in prior["workers"].values():
            pid = wrec.get("pid")
            if isinstance(pid, int) and pid > 1:
                try:
                    os.kill(pid, signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    pass

    try:
        from cubed_tpu.runtime.journal import load_journal

        # phase 1: uninterrupted baseline (journal + control log armed,
        # like the real run, so their overhead is in both numbers)
        out = subprocess.run(
            [sys.executable, "-c", script, "full"], env=env,
            capture_output=True, text=True,
            timeout=max(10.0, deadline - time.monotonic()),
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"uninterrupted run failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        full = json.loads(out.stdout.strip().splitlines()[-1])
        _reap_fleet()
        os.unlink(journal)  # phase 2 writes fresh logs
        shutil.rmtree(control_dir, ignore_errors=True)

        # phase 2: the same compute, the coordinator PROCESS hard-killed
        # at ~50% — NOT its process group: the local worker subprocesses
        # must survive as the orphaned fleet the successor adopts
        proc = subprocess.Popen(
            [sys.executable, "-c", script, "run"], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        t0 = time.perf_counter()
        killed = False
        try:
            while time.monotonic() < deadline and proc.poll() is None:
                if os.path.exists(journal) and len(
                    load_journal(journal)["completed"]
                ) >= RECOVERY_TASKS // 2 + 1:
                    os.kill(proc.pid, signal.SIGKILL)
                    killed = True
                    break
                time.sleep(0.05)
            run_to_kill = time.perf_counter() - t0
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        if not killed:
            raise RuntimeError("compute finished before the kill landed")

        # phase 3: the successor adopts the live fleet and finishes
        out = subprocess.run(
            [sys.executable, "-c", script, "adopt"], env=env,
            capture_output=True, text=True,
            timeout=max(10.0, deadline - time.monotonic()),
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"takeover failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        adopt = json.loads(out.stdout.strip().splitlines()[-1])
        failover_total = run_to_kill + adopt["elapsed"]
        rec = {
            # the gated number: kill-at-50% + live takeover, end to end
            "elapsed": failover_total,
            "uninterrupted_s": full["elapsed"],
            "interrupted_run_s": run_to_kill,
            "takeover_s": adopt["elapsed"],
            "failover_overhead_x": (
                failover_total / full["elapsed"] if full["elapsed"] else None
            ),
            "takeovers": adopt["takeovers"],
            "tasks_readopted": adopt["readopted"],
            "workers_lost": adopt["workers_lost"],
            "tasks_skipped_resume": adopt["tasks_skipped_resume"],
            "resumed_tasks": adopt["resumed_tasks"],
            "total_tasks": adopt["total"],
        }
        print(
            f"coordinator failover: uninterrupted {full['elapsed']:.2f}s, "
            f"kill@50%+takeover {failover_total:.2f}s "
            f"({adopt['readopted']} readopted, "
            f"workers_lost={adopt['workers_lost']})",
            file=sys.stderr, flush=True,
        )
        return rec
    except Exception as e:
        print(f"coordinator failover sweep skipped: {e}", file=sys.stderr)
        return None
    finally:
        _reap_fleet()
        shutil.rmtree(work_dir, ignore_errors=True)


#: p2p-transfer workload: a deep elementwise chain on the fleet — every
#: inter-op edge is one store write+read round-trip per chunk without peer
#: transfer, and (depth-1)/depth of the reads are cache-servable with it
P2P_DEPTH = 6
P2P_N = 16
P2P_CHUNK = 4

P2P_TRANSFER = r"""
import json, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor

DEPTH, N, CHUNK = {depth!r}, {n!r}, {chunk!r}


def bump(x):
    return x + 1.0


an = np.arange(N * N, dtype=np.float64).reshape(N, N)
out = {{}}
for mode in ("store_only", "peer"):
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB",
                   scheduler="dataflow")
    a = ct.from_array(an, chunks=(CHUNK, CHUNK), spec=spec)
    r = a
    for _ in range(DEPTH):
        r = ct.map_blocks(bump, r, dtype=np.float64)
    ex = DistributedDagExecutor(
        n_local_workers=2, peer_transfer=(mode == "peer")
    )
    try:
        ex._ensure_fleet()  # boot outside the timed window
        reg = get_registry()
        before = reg.snapshot()
        t0 = time.perf_counter()
        # optimize_graph=False keeps the chain DEEP (fusion would collapse
        # it into one op and remove the inter-op edges being measured)
        val = np.asarray(r.compute(executor=ex, optimize_graph=False))
        elapsed = time.perf_counter() - t0
        delta = reg.snapshot_delta(before)
    finally:
        ex.close()
    assert (val == an + DEPTH).all()
    out[mode] = {{
        "elapsed": elapsed,
        "bytes_read": delta.get("bytes_read", 0),
        "store_read_bytes_saved": delta.get("store_read_bytes_saved", 0),
        "peer_hits": delta.get("peer_hits", 0),
        "peer_misses": delta.get("peer_misses", 0),
        "peer_bytes_fetched": delta.get("peer_bytes_fetched", 0),
        "peer_fetch_fallbacks": delta.get("peer_fetch_fallbacks", 0),
        "placement_locality_hits": delta.get("placement_locality_hits", 0),
    }}
    print("p2p", mode, round(elapsed, 2), "s", file=sys.stderr, flush=True)
hits = out["peer"]["peer_hits"]
misses = out["peer"]["peer_misses"]
out["hit_rate"] = hits / max(hits + misses, 1)
# the headline: fraction of the store-only read volume the caches absorbed
out["saved_fraction"] = out["peer"]["store_read_bytes_saved"] / max(
    out["store_only"]["bytes_read"], 1
)
print(json.dumps(out), flush=True)
"""


def measure_p2p_transfer(timeout: float):
    """Deep-chain fleet run, store-only vs peer-transfer-enabled.

    Same plan twice on a 2-worker local fleet under the dataflow
    scheduler: once with the historical store-only data plane, once with
    the p2p chunk cache + locality placement. Records wall clock per mode,
    the peer hit rate, and ``saved_fraction`` — ``store_read_bytes_saved``
    over the store-only run's ``bytes_read`` (the acceptance bar is
    >=30%). Rides the same history/perf-gate pipeline as every other
    config. Returns None on failure — additive, never the reason a bench
    run dies."""
    script = P2P_TRANSFER.format(
        repo=REPO, depth=P2P_DEPTH, n=P2P_N, chunk=P2P_CHUNK,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"p2p transfer failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            f"p2p transfer: saved_fraction {res['saved_fraction']:.0%}, "
            f"hit rate {res['hit_rate']:.0%}, "
            f"wall {res['store_only']['elapsed']:.2f}s store-only vs "
            f"{res['peer']['elapsed']:.2f}s peer",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"p2p transfer sweep skipped: {e}", file=sys.stderr)
        return None


#: rechunk-shuffle workload: a transpose-heavy pipeline (two all-to-all
#: rechunks between elementwise maps) where the rechunk exchange
#: dominates bytes moved — the last store round-trip the peer data plane
#: kills. allowed_mem is sized so the copy regions stay strips (several
#: shuffle tasks per stage) instead of consolidating into one whole-array
#: copy
RECHUNK_N = 128
RECHUNK_CHUNK = 32
RECHUNK_ALLOWED = "700KB"

RECHUNK_SHUFFLE = r"""
import json, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.dataflow import build_chunk_graph
from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor

N, CHUNK, ALLOWED = {n!r}, {chunk!r}, {allowed!r}


def bump(x):
    return x + 1.0


an = np.arange(N * N, dtype=np.float64).reshape(N, N)
out = {{}}


def build(mode):
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem=ALLOWED)
    a = ct.from_array(an, chunks=(CHUNK, N), spec=spec)
    r = ct.map_blocks(bump, a, dtype=np.float64)
    r = r.rechunk((N, CHUNK))          # row chunks -> column chunks
    r = ct.map_blocks(bump, r, dtype=np.float64)
    r = r.rechunk((CHUNK, N))          # ... and back: transpose-heavy
    r = ct.map_blocks(bump, r, dtype=np.float64)
    return r


for mode in ("store_only", "peer"):
    if mode == "store_only":
        # the acceptance fact the scheduler is on the hook for: the
        # chunk graph classifies every rechunk stage as chunked, never a
        # barrier (recorded into BENCH_METRICS.json, asserted in tests)
        g = build_chunk_graph(
            build(mode).plan._finalize(optimize_graph=False).dag
        )
        rechunk_kinds = [
            k for n_, k in g.op_kind.items() if "rechunk" in n_
        ]
        out["rechunk_chunked"] = bool(rechunk_kinds) and all(
            k == "rechunk" for k in rechunk_kinds
        ) and not any("rechunk" in n_ for n_ in g.barrier_ops)
    # best-of-2: these computes are sub-second, and container scheduling
    # noise would otherwise drown the wall-clock comparison
    best = None
    for _attempt in range(2):
        r = build(mode)
        ex = DistributedDagExecutor(
            n_local_workers=2, peer_transfer=(mode == "peer")
        )
        try:
            ex._ensure_fleet()  # boot outside the timed window
            reg = get_registry()
            before = reg.snapshot()
            t0 = time.perf_counter()
            # optimize_graph=False keeps the maps unfused so the exchange
            # stages read real intermediate arrays
            val = np.asarray(r.compute(executor=ex, optimize_graph=False))
            elapsed = time.perf_counter() - t0
            delta = reg.snapshot_delta(before)
        finally:
            ex.close()
        assert (val == an + 3.0).all()
        rec = {{
            "elapsed": elapsed,
            "bytes_read": delta.get("bytes_read", 0),
            "store_read_bytes_saved": delta.get(
                "store_read_bytes_saved", 0
            ),
            "peer_hits": delta.get("peer_hits", 0),
            "peer_misses": delta.get("peer_misses", 0),
            "peer_bytes_fetched": delta.get("peer_bytes_fetched", 0),
            "peer_range_fetches": delta.get("peer_range_fetches", 0),
            "shuffle_bytes_peer": delta.get("shuffle_bytes_peer", 0),
            "peer_fetch_fallbacks": delta.get("peer_fetch_fallbacks", 0),
            "placement_locality_hits": delta.get(
                "placement_locality_hits", 0
            ),
        }}
        if best is None or rec["elapsed"] < best["elapsed"]:
            best = rec
    out[mode] = best
    print("rechunk_shuffle", mode, round(best["elapsed"], 2), "s",
          file=sys.stderr, flush=True)
hits = out["peer"]["peer_hits"]
misses = out["peer"]["peer_misses"]
out["hit_rate"] = hits / max(hits + misses, 1)
# the headline: fraction of the store-only read volume the peer-routed
# shuffle eliminated (the acceptance bar is >=40%)
out["saved_fraction"] = out["peer"]["store_read_bytes_saved"] / max(
    out["store_only"]["bytes_read"], 1
)
out["wall_ratio"] = out["peer"]["elapsed"] / max(
    out["store_only"]["elapsed"], 1e-9
)
print(json.dumps(out), flush=True)
"""


def measure_rechunk_shuffle(timeout: float):
    """Transpose-heavy (rechunk-dominated) fleet run, store-only vs
    peer-shuffle.

    Same plan twice on a 2-worker local fleet under the default dataflow
    scheduler: once with every rechunk byte round-tripping through the
    store, once with the all-to-all routed over the peer data plane
    (sub-chunk range fetches + locality-placed fan-in). Records wall
    clock per mode, ``saved_fraction`` (store read bytes eliminated; the
    acceptance bar is >=40%), and ``rechunk_chunked`` (the chunk graph
    classified every rechunk stage as chunked). Rides the same
    history/perf-gate pipeline as ``p2p_transfer``. Returns None on
    failure — additive, never the reason a bench run dies."""
    script = RECHUNK_SHUFFLE.format(
        repo=REPO, n=RECHUNK_N, chunk=RECHUNK_CHUNK, allowed=RECHUNK_ALLOWED,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"rechunk shuffle failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            f"rechunk shuffle: saved_fraction {res['saved_fraction']:.0%}, "
            f"hit rate {res['hit_rate']:.0%}, "
            f"{res['peer']['peer_range_fetches']} range fetch(es), "
            f"rechunk_chunked={res['rechunk_chunked']}, "
            f"wall {res['store_only']['elapsed']:.2f}s store-only vs "
            f"{res['peer']['elapsed']:.2f}s peer",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"rechunk shuffle sweep skipped: {e}", file=sys.stderr)
        return None


#: telemetry-overhead config: the scheduler deep chain (same shape, no
#: injected straggler — sleep would mask sampler cost) run twice, live
#: telemetry off vs armed (1s sampler + HTTP endpoint + a 0.5s scraper
#: hitting /metrics throughout), so the "on" wall clock carries the whole
#: observation cost a production scrape would
TELEMETRY_OVERHEAD = r"""
import json, os, sys, tempfile, threading, time, urllib.request
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor

DEPTH, N, CHUNK = {depth!r}, {n!r}, {chunk!r}

# an operator's scrape config must not arm the OFF mode (the runbook in
# docs/operations.md exports this var fleet-wide); the ON mode sets it
# explicitly below so Plan.execute takes the REAL production arming path
# (incl. the per-task progress callback), not a test shortcut
os.environ.pop("CUBED_TPU_TELEMETRY_PORT", None)


def bump(x):
    return x + 1.0


an = np.arange(N * N, dtype=np.float64).reshape(N, N)


def run_chain():
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB")
    a = ct.from_array(an, chunks=(CHUNK, CHUNK), spec=spec)
    r = a
    for _ in range(DEPTH):
        r = ct.map_blocks(bump, r, dtype=np.float64)
    t0 = time.perf_counter()
    val = np.asarray(r.compute(executor=AsyncPythonDagExecutor(),
                               optimize_graph=False))
    elapsed = time.perf_counter() - t0
    assert (val == an + DEPTH).all()
    return elapsed


run_chain()  # warm-up outside both timed windows (imports, tracing, IO)
out = {{}}
for mode in ("off", "on"):
    scrape_stop = None
    if mode == "on":
        from cubed_tpu.observability import export

        # the env var is how production arms it: Plan.execute resolves it,
        # attaches the progress callback, and adopts this same runtime
        os.environ["CUBED_TPU_TELEMETRY_PORT"] = "0"
        rt = export.ensure_started(0)
        scrape_stop = threading.Event()

        def scrape():
            url = f"http://127.0.0.1:{{rt.port}}/metrics"
            while not scrape_stop.wait(0.5):
                try:
                    urllib.request.urlopen(url, timeout=2).read()
                except OSError:
                    pass

        threading.Thread(target=scrape, daemon=True).start()
    # best-of-3 per mode: this chain is sub-second, and scheduling noise
    # on a small container would otherwise drown the number being measured
    elapsed = min(run_chain() for _ in range(3))
    if scrape_stop is not None:
        scrape_stop.set()
    out[mode] = {{"elapsed": elapsed}}
    print("telemetry", mode, round(elapsed, 3), "s",
          file=sys.stderr, flush=True)
off_s = max(out["off"]["elapsed"], 1e-9)
out["overhead_pct"] = (out["on"]["elapsed"] - off_s) / off_s * 100.0
# the generic perf gate reads this key: the ARMED wall clock is the one
# that must not regress (it contains the off cost plus the telemetry tax)
out["elapsed"] = out["on"]["elapsed"]
print(json.dumps(out), flush=True)
"""


def measure_telemetry_overhead(timeout: float):
    """Deep-chain wall clock, live telemetry armed vs off.

    Records ``{"off": {...}, "on": {...}, "overhead_pct": x, "elapsed":
    on_wall}`` into BENCH_METRICS.json as ``telemetry_overhead``; the
    top-level ``elapsed`` rides the generic >20% perf gate, so the armed
    path must stay within wall-clock noise of unobserved runs forever.
    Returns None on failure — additive, never the reason a bench run
    dies."""
    script = TELEMETRY_OVERHEAD.format(
        repo=REPO, depth=SCHED_DEPTH, n=SCHED_N, chunk=SCHED_CHUNK,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"telemetry overhead failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            f"telemetry overhead: {res['overhead_pct']:+.1f}% "
            f"({res['off']['elapsed']:.2f}s off -> "
            f"{res['on']['elapsed']:.2f}s armed)",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"telemetry overhead sweep skipped: {e}", file=sys.stderr)
        return None


#: dispatch-profiler-overhead config: the same deep chain run twice, the
#: coordinator self-profiler (~75 Hz sys._current_frames sampler) off vs
#: armed via the production env-var path — the issue's acceptance bar is
#: that arming costs <5% wall, and the armed elapsed riding the generic
#: perf gate keeps that from rotting
DISPATCH_PROFILE_OVERHEAD = r"""
import json, os, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor

DEPTH, N, CHUNK = {depth!r}, {n!r}, {chunk!r}

# the OFF mode must be the true default (a leaked operator env var would
# arm both halves and hide the tax); the ON mode sets the var explicitly
# below so Plan.execute takes the REAL arming path — profile_enabled() ->
# profile_scoped() -> a sampler thread per compute
os.environ.pop("CUBED_TPU_DISPATCH_PROFILE", None)


def bump(x):
    return x + 1.0


an = np.arange(N * N, dtype=np.float64).reshape(N, N)


def run_chain():
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB")
    a = ct.from_array(an, chunks=(CHUNK, CHUNK), spec=spec)
    r = a
    for _ in range(DEPTH):
        r = ct.map_blocks(bump, r, dtype=np.float64)
    t0 = time.perf_counter()
    val = np.asarray(r.compute(executor=AsyncPythonDagExecutor(),
                               optimize_graph=False))
    elapsed = time.perf_counter() - t0
    assert (val == an + DEPTH).all()
    return elapsed


run_chain()  # warm-up outside both timed windows (imports, tracing, IO)
out = {{}}
for mode in ("off", "on"):
    if mode == "on":
        os.environ["CUBED_TPU_DISPATCH_PROFILE"] = "1"
    # best-of-3 per mode: the chain is sub-second and container
    # scheduling noise would otherwise drown a <5% tax
    elapsed = min(run_chain() for _ in range(3))
    out[mode] = {{"elapsed": elapsed}}
    print("dispatch profile", mode, round(elapsed, 3), "s",
          file=sys.stderr, flush=True)
off_s = max(out["off"]["elapsed"], 1e-9)
out["overhead_pct"] = (out["on"]["elapsed"] - off_s) / off_s * 100.0
# the generic perf gate reads this key: the ARMED wall clock is the one
# that must not regress (it contains the off cost plus the sampler tax)
out["elapsed"] = out["on"]["elapsed"]
print(json.dumps(out), flush=True)
"""


def measure_dispatch_profile_overhead(timeout: float):
    """Deep-chain wall clock, coordinator self-profiler armed vs off.

    Records ``{"off": {...}, "on": {...}, "overhead_pct": x, "elapsed":
    on_wall}`` into BENCH_METRICS.json as ``dispatch_profile_overhead``;
    the top-level ``elapsed`` rides the generic >20% perf gate, so the
    armed sampler must stay within wall-clock noise of unprofiled runs
    forever (the issue's <5% bar, with gate headroom for container
    noise). Returns None on failure — additive, never the reason a
    bench run dies."""
    script = DISPATCH_PROFILE_OVERHEAD.format(
        repo=REPO, depth=SCHED_DEPTH, n=SCHED_N, chunk=SCHED_CHUNK,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"dispatch profile overhead failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            f"dispatch profile overhead: {res['overhead_pct']:+.1f}% "
            f"({res['off']['elapsed']:.2f}s off -> "
            f"{res['on']['elapsed']:.2f}s armed)",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"dispatch profile overhead sweep skipped: {e}",
              file=sys.stderr)
        return None


ANALYTICS_OVERHEAD = r"""
import json, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability import TraceCollector
from cubed_tpu.observability.analytics import analyze
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor

DEPTH, N, CHUNK = {depth!r}, {n!r}, {chunk!r}


def bump(x):
    return x + 1.0


an = np.arange(N * N, dtype=np.float64).reshape(N, N)


def run_chain(collector=None):
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB",
                   scheduler="dataflow")
    a = ct.from_array(an, chunks=(CHUNK, CHUNK), spec=spec)
    r = a
    for _ in range(DEPTH):
        r = ct.map_blocks(bump, r, dtype=np.float64)
    callbacks = [collector] if collector is not None else None
    t0 = time.perf_counter()
    val = np.asarray(r.compute(executor=AsyncPythonDagExecutor(),
                               callbacks=callbacks, optimize_graph=False))
    elapsed = time.perf_counter() - t0
    analyze_s = 0.0
    if collector is not None:
        t1 = time.perf_counter()
        rep = analyze(collector)
        analyze_s = time.perf_counter() - t1
        assert rep.to_dict()["critical_path"], "empty critical path"
    assert (val == an + DEPTH).all()
    return elapsed, analyze_s


run_chain()  # warm-up outside both timed windows (imports, tracing, IO)
out = {{}}
# best-of-3 per mode (sub-second chain; scheduling noise would otherwise
# drown the tax being measured). ARMED = a TraceCollector attached (span
# recording + chunk-graph capture active) and analyze() run post-compute
# — the full analytics cost a compute pays when someone is watching
for mode in ("off", "on"):
    best = None
    for _ in range(3):
        collector = TraceCollector(trace_dir=None) if mode == "on" else None
        elapsed, analyze_s = run_chain(collector)
        total = elapsed + analyze_s
        if best is None or total < best[0]:
            best = (total, elapsed, analyze_s)
    out[mode] = {{"elapsed": best[1], "analyze_s": best[2]}}
    print("analytics", mode, round(best[0], 3), "s",
          file=sys.stderr, flush=True)
off_s = max(out["off"]["elapsed"], 1e-9)
on_total = out["on"]["elapsed"] + out["on"]["analyze_s"]
out["overhead_pct"] = (on_total - off_s) / off_s * 100.0
out["analyze_s"] = out["on"]["analyze_s"]
# the generic perf gate reads this key: the ARMED total (compute with the
# collector attached + the analyze() pass) is what must not regress
out["elapsed"] = on_total
print(json.dumps(out), flush=True)
"""


STORE_BROWNOUT = r"""
import itertools, json, os, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu import utils as ct_utils
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor
from cubed_tpu.runtime.resilience import RetryPolicy
from cubed_tpu.storage import health

N, CHUNK, RATE = 24, 2, 0.25
an = np.arange(N * N, dtype=np.float64).reshape(N, N)


def run(base):
    # pinned gensym names: both modes must roll IDENTICAL seeded
    # throttle decisions (chunk keys embed the array names)
    ct_utils.sym_counter = itertools.count(base)
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB",
                   fault_injection=dict(seed=23, storage_throttle_rate=RATE))
    a = ct.from_array(an, chunks=(CHUNK, CHUNK), spec=spec)
    b = a * 2.0 + 1.0
    before = get_registry().snapshot()
    t0 = time.perf_counter()
    val = np.asarray(b.compute(
        executor=AsyncPythonDagExecutor(
            max_workers=4,
            retry_policy=RetryPolicy(retries=6, backoff_base=0.01, seed=0),
        ),
    ))
    elapsed = time.perf_counter() - t0
    assert (val == an * 2.0 + 1.0).all(), "brownout result not bitwise"
    d = get_registry().snapshot_delta(before)
    return {{
        "elapsed": elapsed,
        "task_retries": int(d.get("task_retries", 0) or 0),
        "store_throttled": int(d.get("store_throttled", 0) or 0),
        "store_breaker_trips": int(d.get("store_breaker_trips", 0) or 0),
    }}


out = {{}}
os.environ[health.BREAKER_ENV_VAR] = "off"
out["breaker_off"] = run(90_000)
health.reset_breakers()
os.environ.pop(health.BREAKER_ENV_VAR, None)
out["breaker_on"] = run(90_000)
out["retry_draw_saved"] = (
    out["breaker_off"]["task_retries"] - out["breaker_on"]["task_retries"]
)
# the generic perf gate reads this key: the breaker-ON wall clock under
# a seeded brownout is what must not regress
out["elapsed"] = out["breaker_on"]["elapsed"]
print(json.dumps(out), flush=True)
"""


CHAOS_DEGRADATION = r"""
import itertools, json, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu import utils as ct_utils
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor
from cubed_tpu.runtime.resilience import RetryPolicy

N, CHUNK, DEPTH = 24, 2, 4
an = np.arange(N * N, dtype=np.float64).reshape(N, N)
# the composed schedule: three failure domains at campaign-grade rates
# (storage flakiness + injected task crashes + stragglers), all seeded
FAULTS = dict(seed=1800,
              storage_read_failure_rate=0.08,
              storage_write_failure_rate=0.08,
              task_failure_rate=0.05,
              straggler_rate=0.1, straggler_delay_s=0.02)


def run(base, faults):
    # pinned gensym names: the faulty mode must roll IDENTICAL seeded
    # decisions run over run (chunk keys embed the array names)
    ct_utils.sym_counter = itertools.count(base)
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB",
                   fault_injection=faults)
    a = ct.from_array(an, chunks=(CHUNK, CHUNK), spec=spec)
    b = a
    for _ in range(DEPTH):
        b = b * 2.0 + 1.0
    expected = an.copy()
    for _ in range(DEPTH):
        expected = expected * 2.0 + 1.0
    before = get_registry().snapshot()
    t0 = time.perf_counter()
    val = np.asarray(b.compute(
        executor=AsyncPythonDagExecutor(
            max_workers=4,
            retry_policy=RetryPolicy(retries=6, backoff_base=0.01, seed=0),
        ),
    ))
    elapsed = time.perf_counter() - t0
    assert (val == expected).all(), "chaos result not bitwise"
    d = get_registry().snapshot_delta(before)
    return {{
        "elapsed": elapsed,
        "task_retries": int(d.get("task_retries", 0) or 0),
        "faults_injected": int(d.get("faults_injected", 0) or 0),
    }}


out = {{}}
out["clean"] = run(92_000, None)
out["composed"] = run(92_000, FAULTS)
clean_s = max(out["clean"]["elapsed"], 1e-9)
out["degradation_ratio"] = out["composed"]["elapsed"] / clean_s
# the generic perf gate reads this key: the wall clock under composed
# chaos is what must not regress — absorbing the same seeded failure
# load more slowly is a real resilience regression
out["elapsed"] = out["composed"]["elapsed"]
print(json.dumps(out), flush=True)
"""


def measure_chaos_degradation(timeout: float):
    """Composed-failure degradation: the deep elementwise chain clean vs
    under a seeded three-domain schedule (storage flakiness + task
    crashes + stragglers, the campaign-suite shape). Records both wall
    clocks, the retry/injection draw, and the degradation ratio into
    BENCH_METRICS.json as ``chaos_degradation``; the composed wall rides
    the generic >20% perf gate."""
    script = CHAOS_DEGRADATION.format(repo=REPO)
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"chaos degradation failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            "chaos degradation: composed "
            f"{res['composed']['elapsed']:.2f}s "
            f"({res['composed']['faults_injected']} injected / "
            f"{res['composed']['task_retries']} retries) vs clean "
            f"{res['clean']['elapsed']:.2f}s — ratio "
            f"{res['degradation_ratio']:.2f}x",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"chaos degradation sweep skipped: {e}", file=sys.stderr)
        return None


def measure_store_brownout(timeout: float):
    """Seeded store brownout (25% 429/503-shaped throttles), health
    breaker on vs off: retry-budget draw and wall clock for both modes
    into BENCH_METRICS.json as ``store_brownout``. The breaker-on wall
    rides the generic >20% perf gate; the breaker must also draw
    strictly less retry budget than the off baseline (asserted in
    tier-1 chaos, recorded here as a tracked number)."""
    script = STORE_BROWNOUT.format(repo=REPO)
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"store brownout failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            "store brownout: breaker on "
            f"{res['breaker_on']['elapsed']:.2f}s / "
            f"{res['breaker_on']['task_retries']} retries drawn vs off "
            f"{res['breaker_off']['elapsed']:.2f}s / "
            f"{res['breaker_off']['task_retries']} retries "
            f"({res['retry_draw_saved']} saved)",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"store brownout sweep skipped: {e}", file=sys.stderr)
        return None


def measure_analytics_overhead(timeout: float):
    """Deep-chain wall clock, analytics armed (TraceCollector + post-hoc
    ``analyze()``) vs off.

    Records ``{"off": {...}, "on": {...}, "overhead_pct": x, "analyze_s":
    s, "elapsed": armed_total}`` into BENCH_METRICS.json as
    ``analytics_overhead``; the top-level ``elapsed`` rides the generic
    >20% perf gate, so span recording + chunk-graph capture + the
    critical-path pass must stay cheap forever. Returns None on failure —
    additive, never the reason a bench run dies."""
    script = ANALYTICS_OVERHEAD.format(
        repo=REPO, depth=SCHED_DEPTH, n=SCHED_N, chunk=SCHED_CHUNK,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"analytics overhead failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            f"analytics overhead: {res['overhead_pct']:+.1f}% "
            f"({res['off']['elapsed']:.2f}s off -> "
            f"{res['on']['elapsed']:.2f}s armed + "
            f"{res['analyze_s']:.3f}s analyze)",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"analytics overhead sweep skipped: {e}", file=sys.stderr)
        return None


#: multi-tenant service bench: N synthetic tenants sustaining submissions
#: against one threaded service — QPS, latency quantiles, fairness
MT_TENANTS = 3
MT_REQUESTS_PER_TENANT = 8
MT_REPEAT_EVERY = 4  # every 4th submission repeats an earlier query

MULTITENANT_SERVICE = r"""
import json, os, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor
from cubed_tpu.service import ComputeService

TENANTS = {tenants!r}
R = {requests!r}
REPEAT = {repeat!r}

an = np.arange(64 * 64, dtype=np.float64).reshape(64, 64)
spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB")


def build(k):
    def kernel(x, _k=float(k)):
        return x + _k

    a = ct.from_array(an, chunks=(16, 16), spec=spec)
    return ct.map_blocks(kernel, a, dtype=np.float64)


reg = get_registry()
before = reg.snapshot()
svc = ComputeService(
    executor=AsyncPythonDagExecutor(), max_concurrent=2,
).start()
handles = []
t0 = time.perf_counter()
try:
    for i in range(R):
        for t in range(TENANTS):
            # every REPEAT-th submission repeats that tenant's first
            # query: the sustained mix exercises the plan/result caches
            k = (t * 1000) + (0 if (i and i % REPEAT == 0) else i)
            handles.append(
                (svc.submit(build(k), tenant=f"tenant-{{t}}"), t, k)
            )
    for h, t, k in handles:
        val = h.result(timeout=600)
        assert (val == an + float(k)).all()
    elapsed = time.perf_counter() - t0
finally:
    svc.close()

lat = sorted(
    (h._request.ended_at - h._request.submitted_at) for h, _, _ in handles
)
per_tenant = {{}}
per_tenant_lat = {{}}
for h, t, _ in handles:
    per_tenant.setdefault(t, []).append(h._request.ended_at)
    per_tenant_lat.setdefault(t, []).append(
        h._request.ended_at - h._request.submitted_at
    )
# per-tenant throughput over the tenant's own submit->last-done window
tps = {{
    t: len(ends) / max(1e-9, max(ends) - t0)
    for t, ends in per_tenant.items()
}}
# per-tenant latency percentiles: the SLO-facing numbers — a regression
# hitting ONE tenant must not hide inside the global percentile
tenants = {{}}
for t, ls in per_tenant_lat.items():
    ls = sorted(ls)
    tenants[f"tenant-{{t}}"] = {{
        "p50_s": ls[len(ls) // 2],
        "p99_s": ls[min(len(ls) - 1, (len(ls) * 99) // 100)],
    }}
delta = reg.snapshot_delta(before)
n = len(handles)
print(json.dumps({{
    "elapsed": elapsed,
    "requests": n,
    "qps": n / max(1e-9, elapsed),
    "p50_s": lat[n // 2],
    "p99_s": lat[min(n - 1, (n * 99) // 100)],
    "fairness_ratio": max(tps.values()) / max(1e-9, min(tps.values())),
    "tenants": tenants,
    "plan_cache_hits": delta.get("plan_cache_hits", 0),
    "result_cache_hits": delta.get("result_cache_hits", 0),
}}), flush=True)
"""


def measure_multitenant_service(timeout: float):
    """Sustained submissions from N synthetic tenants against one
    threaded service: QPS, p50/p99 request latency, and the fairness
    ratio (max/min per-tenant throughput; 1.0 = perfectly fair under the
    equal weights used here). Recorded as ``multitenant_service`` in
    BENCH_METRICS.json — ``elapsed`` and ``qps`` ride the >20% perf gate.
    Returns None on failure — additive, never the reason a bench run
    dies."""
    script = MULTITENANT_SERVICE.format(
        repo=REPO, tenants=MT_TENANTS, requests=MT_REQUESTS_PER_TENANT,
        repeat=MT_REPEAT_EVERY,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"multitenant service failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            f"multitenant service: {res['requests']} requests in "
            f"{res['elapsed']:.2f}s ({res['qps']:.1f} QPS, p50 "
            f"{res['p50_s'] * 1000:.0f}ms, p99 {res['p99_s'] * 1000:.0f}ms, "
            f"fairness {res['fairness_ratio']:.2f}, "
            f"{res['result_cache_hits']} result-cache hit(s))",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"multitenant service sweep skipped: {e}", file=sys.stderr)
        return None


#: SLO/archive overhead A/B: the same 2-tenant request mix against a
#: bare service (off) vs one with the durable run archive + per-tenant
#: SLO board armed (on: service_dir + slos + Spec(run_history=...)) —
#: the SLI record, the fsync'd archive append, and the per-compute
#: analyze() digest must all be wall-clock noise. Requests are 64-task
#: computes (not single-chunk toys): the archive tax is fixed per
#: compute, so the ratio is only meaningful against a request that does
#: representative work
SLO_TENANTS = 2
SLO_REQUESTS_PER_TENANT = 4

SLO_OVERHEAD = r"""
import json, os, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor
from cubed_tpu.service import ComputeService

TENANTS = {tenants!r}
R = {requests!r}

an = np.arange(128 * 128, dtype=np.float64).reshape(128, 128)


def run_mix(spec, **svc_kwargs):
    def build(k):
        def kernel(x, _k=float(k)):
            return x + _k

        a = ct.from_array(an, chunks=(16, 16), spec=spec)
        return ct.map_blocks(kernel, a, dtype=np.float64)

    svc = ComputeService(
        executor=AsyncPythonDagExecutor(), max_concurrent=2,
        result_cache=False, spec=spec, **svc_kwargs,
    ).start()
    t0 = time.perf_counter()
    try:
        handles = [
            svc.submit(build(t * 1000 + i), tenant=f"tenant-{{t}}")
            for i in range(R) for t in range(TENANTS)
        ]
        for h in handles:
            h.result(timeout=600)
        return time.perf_counter() - t0
    finally:
        svc.close()


out = {{}}
# warm-up outside both timed windows (imports, tracing, first zarr IO)
run_mix(ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB"))
for mode in ("off", "on"):
    if mode == "on":
        base = tempfile.mkdtemp()
        spec = ct.Spec(
            work_dir=base, allowed_mem="2GB",
            run_history=os.path.join(base, "hist"),
        )
        kwargs = dict(
            service_dir=os.path.join(base, "svc"),
            slos={{
                f"tenant-{{t}}": {{"latency_s": 30.0,
                                   "availability_objective": 0.999}}
                for t in range(TENANTS)
            }},
        )
    else:
        spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB")
        kwargs = {{}}
    # best-of-3 per mode: sub-second mixes, scheduling noise would
    # otherwise drown the number being measured
    elapsed = min(run_mix(spec, **kwargs) for _ in range(3))
    out[mode] = {{"elapsed": elapsed}}
    print("slo", mode, round(elapsed, 3), "s", file=sys.stderr, flush=True)
off_s = max(out["off"]["elapsed"], 1e-9)
out["overhead_pct"] = (out["on"]["elapsed"] - off_s) / off_s * 100.0
# the generic perf gate reads this key: the ARMED wall clock is the one
# that must not regress (it contains the off cost plus the SLO/archive tax)
out["elapsed"] = out["on"]["elapsed"]
print(json.dumps(out), flush=True)
"""


def measure_slo_overhead(timeout: float):
    """Service request mix, SLO board + durable run archive armed vs off.

    Records ``{"off": {...}, "on": {...}, "overhead_pct": x, "elapsed":
    on_wall}`` into BENCH_METRICS.json as ``slo_overhead``; the armed
    elapsed rides the generic >20% perf gate, so the per-request SLI
    record, the fsync'd ``runs.jsonl`` append, and the per-compute
    ``analyze()`` digest must stay within wall-clock noise forever.
    Returns None on failure — additive, never the reason a bench run
    dies."""
    script = SLO_OVERHEAD.format(
        repo=REPO, tenants=SLO_TENANTS, requests=SLO_REQUESTS_PER_TENANT,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            env=_cpu_env(),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"slo overhead failed (rc={out.returncode}): "
                f"{out.stderr[-2000:]}"
            )
        res = json.loads(out.stdout.strip().splitlines()[-1])
        print(
            f"slo overhead: {res['overhead_pct']:+.1f}% "
            f"({res['off']['elapsed']:.2f}s off -> "
            f"{res['on']['elapsed']:.2f}s armed)",
            file=sys.stderr, flush=True,
        )
        return res
    except Exception as e:
        print(f"slo overhead sweep skipped: {e}", file=sys.stderr)
        return None


#: overload-shedding bench: 2 tenants at ~2x the service's capacity, the
#: degradation ladder on vs CUBED_TPU_OVERLOAD=off — goodput is requests
#: that SUCCEEDED (deadline met) per second; shed-on must beat shed-off
OVL_TASK_S = 0.08         # per-request kernel sleep (1 chunk = 1 task)
OVL_N_PER_TENANT = 16     # submissions per tenant (2 tenants)
OVL_SUBMIT_GAP_S = 0.04   # ~2x overload vs the single admission slot
OVL_DEADLINE_S = 0.5

OVERLOAD_SHEDDING = r"""
import json, os, sys, tempfile, time
sys.path.insert(0, {repo!r})
import numpy as np
import cubed_tpu as ct
from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor
from cubed_tpu.service import (
    ComputeService, OverloadPolicy, ServiceOverloadedError,
)

TASK_S = {task_s!r}
N = {n!r}
GAP = {gap!r}
DEADLINE = {deadline!r}

an = np.arange(16, dtype=np.float64).reshape(4, 4)
spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="2GB")


def build(k):
    def kernel(x, _k=float(k)):
        time.sleep(TASK_S)
        return x + _k

    a = ct.from_array(an, chunks=(4, 4), spec=spec)
    return ct.map_blocks(kernel, a, dtype=np.float64)


svc = ComputeService(
    executor=AsyncPythonDagExecutor(),
    max_concurrent=1,
    result_cache=False,  # every request must EXECUTE (goodput, not reuse)
    overload_policy=OverloadPolicy(
        queue_l1=2, queue_l2=4, queue_l3=1000,
        down_dwell_s=10.0, tick_interval_s=0.02,
    ),
    breaker_threshold=3, breaker_cooldown_s=0.5,
).start()
handles, shed = [], 0
t0 = time.perf_counter()
try:
    for i in range(N):
        for tenant, klass in (("slo", "interactive"), ("bulk", "batch")):
            try:
                handles.append(svc.submit(
                    build(i * 10 + (tenant == "bulk")), tenant=tenant,
                    deadline_s=DEADLINE, request_class=klass,
                ))
            except ServiceOverloadedError:
                shed += 1
        time.sleep(GAP)
    ok = failed = 0
    for h in handles:
        try:
            h.result(timeout=600)
            ok += 1
        except ServiceOverloadedError:
            shed += 1
        except Exception:
            failed += 1  # deadline blown (or aborted mid-run)
    elapsed = time.perf_counter() - t0
    ovl = svc.stats_snapshot()["overload"]
finally:
    svc.close()

print(json.dumps({{
    "elapsed": elapsed,
    "submitted": 2 * N,
    "ok": ok,
    "shed": shed,
    "failed": failed,
    "goodput": ok / max(1e-9, elapsed),
    "overload_enabled": ovl["enabled"],
    "max_level_seen": ovl.get("level", 0),
    "transitions": ovl.get("transitions", 0),
}}), flush=True)
"""


def measure_overload_shedding(timeout: float):
    """Two tenants at ~2x capacity against a one-slot service, run twice:
    degradation ladder ON, then ``CUBED_TPU_OVERLOAD=off``. Goodput is
    deadline-met successes per second — shedding trades rejected requests
    (fast, typed, retry-after attached) for requests that finish on time,
    so ``goodput_on`` must beat ``goodput_off``. Recorded as
    ``overload_shedding`` in BENCH_METRICS.json; the intra-run ratio and
    the goodput_on trajectory ride the perf gate. Returns None on
    failure — additive, never the reason a bench run dies."""
    script = OVERLOAD_SHEDDING.format(
        repo=REPO, task_s=OVL_TASK_S, n=OVL_N_PER_TENANT,
        gap=OVL_SUBMIT_GAP_S, deadline=OVL_DEADLINE_S,
    )
    try:
        arms = {}
        for arm in ("on", "off"):
            env = _cpu_env()
            if arm == "off":
                env["CUBED_TPU_OVERLOAD"] = "off"
            out = subprocess.run(
                [sys.executable, "-c", script],
                env=env,
                capture_output=True,
                text=True,
                timeout=timeout / 2,
            )
            if out.returncode != 0:
                raise RuntimeError(
                    f"overload arm {arm} failed (rc={out.returncode}): "
                    f"{out.stderr[-2000:]}"
                )
            arms[arm] = json.loads(out.stdout.strip().splitlines()[-1])
        on, off = arms["on"], arms["off"]
        res = {
            "elapsed": on["elapsed"] + off["elapsed"],
            "goodput_on": on["goodput"],
            "goodput_off": off["goodput"],
            "goodput_ratio": on["goodput"] / max(1e-9, off["goodput"]),
            "shed_on": on["shed"],
            "failed_on": on["failed"],
            "failed_off": off["failed"],
            "max_level_seen": on["max_level_seen"],
            "transitions": on["transitions"],
        }
        print(
            f"overload shedding: goodput {res['goodput_on']:.2f}/s (ladder "
            f"on, {on['ok']} ok / {on['shed']} shed / {on['failed']} "
            f"failed) vs {res['goodput_off']:.2f}/s (off, {off['ok']} ok / "
            f"{off['failed']} failed) — ratio "
            f"{res['goodput_ratio']:.2f}x, peak L{on['max_level_seen']}",
            file=sys.stderr, flush=True,
        )
        if res["goodput_ratio"] < 1.0:
            print(
                "OVERLOAD REGRESSION: shedding did not beat the off arm "
                f"(ratio {res['goodput_ratio']:.2f}x)",
                file=sys.stderr,
            )
        return res
    except Exception as e:
        print(f"overload shedding sweep skipped: {e}", file=sys.stderr)
        return None


def _cpu_env() -> dict:
    """Env for the host-only sweeps and numpy baselines: the CPU platform,
    so none of their subprocesses ever reaches for the chip."""
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _run_phase(
    *, env: dict, timeout: float, use_jax_executor: bool, warmup: bool,
    workload: str,
) -> dict:
    script = WORKLOAD.format(
        repo=REPO,
        shape=SHAPE,
        chunk=CHUNK,
        addsum_shape=ADDSUM_SHAPE,
        addsum_chunk=ADDSUM_CHUNK,
        addsum_scaled_shape=ADDSUM_SCALED_SHAPE,
        addsum_scaled_chunk=ADDSUM_SCALED_CHUNK,
        matmul_n=MATMUL_N,
        matmul_chunk=MATMUL_CHUNK,
        elemwise_shape=ELEMWISE_SHAPE,
        elemwise_chunk=ELEMWISE_CHUNK,
        reduce_shape=REDUCE_SHAPE,
        reduce_chunk=REDUCE_CHUNK,
        use_jax_executor=use_jax_executor,
        warmup=warmup,
        workload=workload,
    )
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(f"phase failed (rc={out.returncode}): {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def get_baselines() -> dict:
    """Recorded numpy-executor baselines; measure + record only if absent."""
    rec: dict = {}
    try:
        with open(RECORD_PATH) as f:
            rec = json.load(f)
        if "elapsed" in rec:  # legacy single-config record -> vorticity
            rec = {"vorticity": rec}
    except (OSError, ValueError):
        rec = {}

    changed = False
    for workload, shape, chunk in [
        ("vorticity", SHAPE, CHUNK),
        ("addsum", ADDSUM_SHAPE, ADDSUM_CHUNK),
        ("addsum_scaled", ADDSUM_SCALED_SHAPE, ADDSUM_SCALED_CHUNK),
        ("matmul", (MATMUL_N, MATMUL_N), MATMUL_CHUNK),
        ("elemwise", ELEMWISE_SHAPE, ELEMWISE_CHUNK),
        ("reduce", REDUCE_SHAPE, REDUCE_CHUNK),
    ]:
        entry = rec.get(workload)
        if (
            isinstance(entry, dict)
            and entry.get("shape") == list(shape)
            and entry.get("chunk") == chunk
            and isinstance(entry.get("elapsed"), (int, float))
        ):
            continue
        env = _cpu_env()
        env["CUBED_TPU_BACKEND"] = "numpy"
        try:
            res = _run_phase(
                env=env,
                timeout=_remaining(BASELINE_TIMEOUT_S),
                use_jax_executor=False,
                warmup=False,
                workload=workload,
            )
        except Exception as e:
            print(f"{workload} baseline measurement failed: {e}", file=sys.stderr)
            continue
        rec[workload] = {
            "metric": f"{workload} numpy-backend PythonDagExecutor elapsed",
            "shape": list(shape),
            "chunk": chunk,
            "elapsed": res["elapsed"],
            "value": res["value"],
            "measured": time.strftime("%Y-%m-%d")
            + ", single-process numpy backend, CPU platform",
        }
        changed = True
    if changed:
        try:  # atomic write so a killed run can't leave a corrupt record
            tmp = RECORD_PATH + ".tmp"
            with open(tmp, "w") as f:
                json.dump(rec, f, indent=1)
            os.replace(tmp, RECORD_PATH)
        except OSError:
            pass
    return rec


def emit(metric: str, res: dict, baseline, work: int, unit: str = "GB/s/chip") -> None:
    elapsed = max(res["elapsed"], 1e-9)
    vs = round(baseline["elapsed"] / elapsed, 3) if baseline else None
    line = {
        "metric": metric,
        "value": round(work / elapsed / 1e9, 3),
        "unit": unit,
        "vs_baseline": vs,
        "device": res.get("device"),
    }
    print(json.dumps(line), flush=True)


#: (workload — doubles as the baselines key, metric name, work units, unit,
#: phase timeout)
CONFIGS = [
    ("matmul", "matmul_4000x4000_blockwise_contraction", MATMUL_FLOPS,
     "GFLOP/s/chip", 120),
    ("matmul_bf16", "matmul_4000x4000_bf16_mxu", MATMUL_FLOPS,
     "GFLOP/s/chip", 120),
    ("elemwise", "elementwise_chain_6000x6000_f64", ELEMWISE_WORK_BYTES,
     "GB/s/chip", 120),
    ("reduce", "axis_reductions_8000x8000_f64", REDUCE_WORK_BYTES,
     "GB/s/chip", 120),
    ("addsum", "blockwise_addsum_5000x5000_f64", ADDSUM_WORK_BYTES,
     "GB/s/chip", 120),
    # physical bytes under f32 ingestion are half the declared-f64 bytes
    ("vorticity_f32", "pangeo_vorticity_500x450x400_f32_ingest",
     WORK_BYTES // 2, "GB/s/chip", 120),
    ("addsum_scaled", "blockwise_addsum_16000x16000_f64_scaled",
     ADDSUM_SCALED_WORK_BYTES, "GB/s/chip", 120),
    # vorticity LAST (the driver parses the last line)
    ("vorticity", "pangeo_vorticity_500x450x400_f64_throughput", WORK_BYTES,
     "GB/s/chip", 300),
]

#: precision-opt-in variants compare against their full-precision config's
#: numpy baseline (the speedup the opt-in buys over the same reference math)
BASELINE_KEY = {"matmul_bf16": "matmul", "vorticity_f32": "vorticity"}


def main() -> None:
    baselines = get_baselines()

    # device phases, one after another, each owning the chip in turn; a
    # failed phase is reported, the rest still run, and the run exits
    # non-zero at the end
    failed_phases: list = []
    metrics_record: dict = {}
    for workload, metric, work, unit, timeout in CONFIGS:
        try:
            res = _run_phase(
                env=dict(os.environ),
                timeout=_remaining(timeout),
                use_jax_executor=True,
                warmup=True,
                workload=workload,
            )
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            print(f"{workload} device phase FAILED: {str(e)[:1200]}",
                  file=sys.stderr)
            failed_phases.append(workload)
        else:
            base = baselines.get(BASELINE_KEY.get(workload, workload))
            emit(metric, res, base, work, unit=unit)
            stats = res.get("executor_stats") or {}
            metrics_record[metric] = {
                "elapsed": res.get("elapsed"),
                "value": res.get("value"),
                # resilience trajectory: retry overhead and injected faults
                # ride alongside the perf numbers so a regression in either
                # is visible from BENCH_METRICS.json history alone
                "task_retries": stats.get("task_retries", 0),
                "faults_injected": stats.get("faults_injected", 0),
                # integrity trajectory: verification volume, detected
                # corruption, and resume's chunk-granular skips
                "chunks_verified": stats.get("chunks_verified", 0),
                "chunks_corrupt_detected": stats.get(
                    "chunks_corrupt_detected", 0
                ),
                "tasks_skipped_resume": stats.get("tasks_skipped_resume", 0),
                # memory-guard trajectory: observe-mode exceedances,
                # admission throttling, and peak worker RSS per config —
                # guard overhead or pressure regressions show up here
                # before anyone has to profile (the sampler must stay <2%
                # wall-clock on the threaded bench, visible via elapsed)
                "mem_guard_soft_exceeded": stats.get(
                    "mem_guard_soft_exceeded", 0
                ),
                "tasks_throttled": stats.get("tasks_throttled", 0),
                # gauge for in-process/threaded runs, heartbeat gauge for
                # fleets, and per-op worker VmHWM (measured where each task
                # ran, riding TaskEndEvent) for multiprocess pools whose
                # worker-local gauges never reach the client registry
                "worker_rss_peak": (
                    stats.get("worker_rss_bytes_max")
                    or stats.get("fleet_worker_rss_bytes_max")
                    or max(
                        (
                            (row.get("peak_measured_mem") or 0)
                            for row in (stats.get("per_op") or {}).values()
                        ),
                        default=0,
                    )
                ),
                "executor_stats": stats or None,
            }

    # fleet scaling: tasks/sec at 1→2→4→8→16→32 workers, budget
    # permitting — sleep-bound tasks, so the sweep cost is dominated by
    # the 63 worker boots, not compute
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 110:
        scaling = measure_fleet_scaling(_remaining(180))
        if scaling is not None:
            metrics_record["fleet_scaling"] = scaling
    else:
        print("fleet scaling sweep skipped: out of budget", file=sys.stderr)

    # scheduler overlap: the deep-chain critical path, op-level vs
    # dataflow (~DEPTH x DELAY + DELAY of sleeping, well under a minute)
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        sched = measure_scheduler_overlap(_remaining(90))
        if sched is not None:
            metrics_record["scheduler_deepchain"] = sched
    else:
        print("scheduler overlap sweep skipped: out of budget",
              file=sys.stderr)

    # coordinator crash recovery: kill-at-50%-then-resume-from-journal vs
    # an uninterrupted run (three fleet boots + ~3x a short sleep-bound
    # compute); `elapsed` is the recovery total so the generic perf gate
    # flags regressions like any other config
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 75:
        recovery = measure_coordinator_recovery(_remaining(120))
        if recovery is not None:
            metrics_record["coordinator_recovery"] = recovery
    else:
        print("coordinator recovery sweep skipped: out of budget",
              file=sys.stderr)

    # live coordinator failover: SIGKILL the coordinator process at ~50%
    # and let a successor adopt the still-running worker fleet via the
    # control log + rendezvous file; `elapsed` (run-to-kill + takeover)
    # rides the same >20% perf gate, and failover_overhead_x tracks the
    # < 2x-of-uninterrupted acceptance bound
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 75:
        failover = measure_coordinator_failover(_remaining(120))
        if failover is not None:
            metrics_record["coordinator_failover"] = failover
    else:
        print("coordinator failover sweep skipped: out of budget",
              file=sys.stderr)

    # p2p chunk transfer: the deep chain store-only vs peer-enabled (two
    # fleet boots + two short elementwise-chain computes)
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 60:
        p2p = measure_p2p_transfer(_remaining(120))
        if p2p is not None:
            metrics_record["p2p_transfer"] = p2p
    else:
        print("p2p transfer sweep skipped: out of budget", file=sys.stderr)

    # rechunk shuffle: the transpose-heavy pipeline store-only vs the
    # peer-routed all-to-all (two fleet boots + two short computes)
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 60:
        shuf = measure_rechunk_shuffle(_remaining(120))
        if shuf is not None:
            metrics_record["rechunk_shuffle"] = shuf
    else:
        print("rechunk shuffle sweep skipped: out of budget",
              file=sys.stderr)

    # telemetry-sampler overhead: the deep chain with the live-telemetry
    # pipeline armed (1s sampler + scraped /metrics endpoint) vs off —
    # the armed wall clock rides the generic >20% perf gate
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        tele = measure_telemetry_overhead(_remaining(90))
        if tele is not None:
            metrics_record["telemetry_overhead"] = tele
    else:
        print("telemetry overhead sweep skipped: out of budget",
              file=sys.stderr)

    # dispatch-profiler overhead: the deep chain with the coordinator
    # self-profiler armed (~75 Hz sys._current_frames sampler) vs off —
    # the armed wall clock rides the generic >20% perf gate
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        dpo = measure_dispatch_profile_overhead(_remaining(90))
        if dpo is not None:
            metrics_record["dispatch_profile_overhead"] = dpo
    else:
        print("dispatch profile overhead sweep skipped: out of budget",
              file=sys.stderr)

    # analytics overhead: the deep chain with a TraceCollector attached +
    # a post-compute analyze() pass vs unobserved — the armed total rides
    # the generic >20% perf gate
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        ana = measure_analytics_overhead(_remaining(90))
        if ana is not None:
            metrics_record["analytics_overhead"] = ana
    else:
        print("analytics overhead sweep skipped: out of budget",
              file=sys.stderr)

    # store brownout: seeded 429/503 throttles, health breaker on vs off
    # (wall clock + retry-budget draw; the breaker-on wall rides the
    # generic perf gate)
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        brn = measure_store_brownout(_remaining(90))
        if brn is not None:
            metrics_record["store_brownout"] = brn
    else:
        print("store brownout sweep skipped: out of budget",
              file=sys.stderr)

    # chaos degradation: the deep chain clean vs under a composed
    # three-domain fault schedule (the campaign-suite shape) — the
    # composed wall clock rides the generic perf gate
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        chd = measure_chaos_degradation(_remaining(90))
        if chd is not None:
            metrics_record["chaos_degradation"] = chd
    else:
        print("chaos degradation sweep skipped: out of budget",
              file=sys.stderr)

    # multi-tenant service: sustained submissions from N synthetic
    # tenants (QPS, p50/p99 latency, fairness ratio, cache hits) — the
    # front-door overhead number the service is on the hook for
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        mt = measure_multitenant_service(_remaining(90))
        if mt is not None:
            metrics_record["multitenant_service"] = mt
    else:
        print("multitenant service sweep skipped: out of budget",
              file=sys.stderr)

    # SLO/archive overhead: the same request mix with the per-tenant SLO
    # board + durable run archive armed vs off — observing the front door
    # must not slow it down
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        slo = measure_slo_overhead(_remaining(90))
        if slo is not None:
            metrics_record["slo_overhead"] = slo
    else:
        print("slo overhead sweep skipped: out of budget", file=sys.stderr)

    # overload shedding: 2-tenant goodput at ~2x overload, degradation
    # ladder on vs CUBED_TPU_OVERLOAD=off — the robustness win the
    # overload controller is on the hook for (shed-on must beat shed-off)
    if OVERALL_DEADLINE_S - (time.monotonic() - _T0) > 45:
        ovl = measure_overload_shedding(_remaining(90))
        if ovl is not None:
            metrics_record["overload_shedding"] = ovl
    else:
        print("overload shedding sweep skipped: out of budget",
              file=sys.stderr)

    # per-op timing / IO-byte trajectories ride alongside the headline
    # numbers so future rounds can localize regressions without re-profiling
    prev_trajectory = _previous_trajectory()
    record = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"), "configs": metrics_record
    }
    try:
        path = os.path.join(REPO, "BENCH_METRICS.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1, default=str)
        os.replace(tmp, path)
    except OSError as e:
        print(f"could not write BENCH_METRICS.json: {e}", file=sys.stderr)
    _append_history(record)
    _print_trajectory_deltas(metrics_record, prev_trajectory)
    if failed_phases:
        sys.exit(f"device phases failed: {', '.join(failed_phases)}")


#: bound on retained history records (one JSON line per bench run); the
#: perf-regression gate (tests/test_perf_gate.py) compares the last two
HISTORY_PATH = os.path.join(REPO, "BENCH_METRICS_HISTORY.jsonl")
HISTORY_KEEP = 50


def _append_history(record: dict) -> None:
    """Append this run to the rolling history the perf gate reads.

    BENCH_METRICS.json is overwrite-per-run, so by itself a regression is
    only visible to whoever ran both benches; the history file keeps the
    trajectory on disk (bounded), compactly — per-config scalars only,
    no nested executor_stats blobs."""
    slim_cfgs = {}
    for name, cfg in (record.get("configs") or {}).items():
        if not isinstance(cfg, dict):
            continue
        slim = {
            k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str)) or k in (
                "tasks_per_s", "efficiency", "dispatch", "oplevel",
                "dataflow", "tenants",
            )
        }
        slim.pop("executor_stats", None)
        slim_cfgs[name] = slim
    line = json.dumps({"t": record.get("t"), "configs": slim_cfgs},
                      default=str)
    try:
        lines = []
        try:
            with open(HISTORY_PATH) as f:
                lines = [ln for ln in f.read().splitlines() if ln.strip()]
        except OSError:
            pass
        lines.append(line)
        tmp = HISTORY_PATH + ".tmp"
        with open(tmp, "w") as f:
            f.write("\n".join(lines[-HISTORY_KEEP:]) + "\n")
        os.replace(tmp, HISTORY_PATH)
    except OSError as e:
        print(f"could not append BENCH_METRICS_HISTORY.jsonl: {e}",
              file=sys.stderr)


def _previous_trajectory():
    """The most recent prior bench record to compare this run against.

    Prefers a previous ``BENCH_METRICS.json`` (full per-config elapsed +
    peak-RSS), falling back to the newest committed ``BENCH_r*.json``
    driver record (throughput-only, parsed from its emitted tail lines).
    Returns ``(configs_dict, label)``; empty dict when there is nothing.
    """
    path = os.path.join(REPO, "BENCH_METRICS.json")
    try:
        with open(path) as f:
            prev = json.load(f)
        configs = prev.get("configs") or {}
        if configs:
            return configs, f"BENCH_METRICS.json ({prev.get('t', '?')})"
    except (OSError, ValueError):
        pass
    import glob

    best: dict = {}
    label = ""
    for p in sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))):
        try:
            with open(p) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        metrics = {}
        for ln in str(rec.get("tail") or "").splitlines():
            try:
                d = json.loads(ln)
            except ValueError:
                continue
            if isinstance(d, dict) and "metric" in d and "value" in d:
                metrics[d["metric"]] = {"value": d["value"]}
        if metrics:
            best, label = metrics, os.path.basename(p)
    return best, label


def _delta_pct(cur, old):
    if not isinstance(cur, (int, float)) or not isinstance(old, (int, float)):
        return None
    if old == 0:
        return None
    return (cur - old) / old * 100.0


def _print_scaling_deltas(cur: dict, old: dict, label: str) -> None:
    """Fleet-scaling trajectory: per-size tasks/sec and scaling efficiency
    vs the previous record, with a LOUD flag on any >20 % efficiency drop
    — the number the autoscaler/drain machinery is on the hook for, so it
    must not be able to rot silently."""
    tps, eff = cur.get("tasks_per_s") or {}, cur.get("efficiency") or {}
    line = ", ".join(
        f"{n}w {tp:.1f}/s" + (
            f" (eff {eff[n]:.2f})" if n in eff else ""
        )
        for n, tp in sorted(tps.items(), key=lambda kv: int(kv[0]))
    )
    print(f"trajectory fleet_scaling: {line}", file=sys.stderr)
    # the control-plane story behind the efficiency curve: per-size
    # dispatch overhead and peak utilization — the ISSUE-16 measurement
    # substrate the sharded-dispatch refactor will be judged against
    disp = cur.get("dispatch") or {}
    if disp:
        dline = ", ".join(
            f"{n}w "
            + (
                f"{row.get('dispatch_overhead_ms'):.2f}ms/task"
                if isinstance(
                    row.get("dispatch_overhead_ms"), (int, float)
                )
                else "?ms/task"
            )
            + (
                f" util {row.get('dispatch_utilization'):.2f}"
                if isinstance(
                    row.get("dispatch_utilization"), (int, float)
                )
                else ""
            )
            for n, row in sorted(disp.items(), key=lambda kv: int(kv[0]))
            if isinstance(row, dict)
        )
        print(f"trajectory fleet_scaling dispatch: {dline}",
              file=sys.stderr)
    old_tps = old.get("tasks_per_s") or {}
    old_eff = old.get("efficiency") or {}
    if not old_tps:
        print("trajectory fleet_scaling: no prior record to compare "
              f"against in {label}" if label else
              "trajectory fleet_scaling: first record", file=sys.stderr)
        return
    regressed = []
    for size in sorted(eff, key=int):
        pct = _delta_pct(eff.get(size), old_eff.get(size))
        if pct is not None and pct <= -20.0:
            regressed.append(
                f"{size}w efficiency {eff[size]:.2f} vs "
                f"{old_eff[size]:.2f} ({pct:+.1f}%)"
            )
    # absolute throughput at each size backs the efficiency ratios: a run
    # where EVERY size slowed equally keeps its efficiency but is still a
    # fleet-dispatch regression
    for size in sorted(tps, key=int):
        pct = _delta_pct(tps.get(size), old_tps.get(size))
        if pct is not None and pct <= -20.0:
            regressed.append(
                f"{size}w {tps[size]:.1f} tasks/s vs "
                f"{old_tps[size]:.1f} ({pct:+.1f}%)"
            )
    if regressed:
        print(
            "SCALING REGRESSION (>20% vs " + (label or "prior record")
            + "): " + "; ".join(regressed),
            file=sys.stderr,
        )
    else:
        print(f"trajectory fleet_scaling: within 20% of {label}",
              file=sys.stderr)


#: relative change beyond which the perf gate calls a regression (the
#: container's own run-to-run noise is ~±15%)
PERF_GATE_THRESHOLD_PCT = 20.0


def perf_regressions(prev: dict, cur: dict) -> list:
    """Compare two bench records' configs; return regression strings.

    The contract the tier-1 gate (tests/test_perf_gate.py) enforces: no
    config's wall clock grows >20%, no fleet-scaling throughput drops
    >20%, and the dataflow scheduler keeps beating the op barrier within
    20% of its recorded margin. Shared here so bench.py's delta printer
    and the test gate can never disagree about what a regression is."""
    out = []
    pcfgs = prev.get("configs") or {}
    for name, cfg in (cur.get("configs") or {}).items():
        old = pcfgs.get(name)
        if not isinstance(old, dict) or not isinstance(cfg, dict):
            continue
        if name == "fleet_scaling":
            old_tps = old.get("tasks_per_s") or {}
            for size, tp in (cfg.get("tasks_per_s") or {}).items():
                pct = _delta_pct(tp, old_tps.get(size))
                if pct is not None and pct <= -PERF_GATE_THRESHOLD_PCT:
                    out.append(
                        f"fleet_scaling {size}w throughput {tp:.1f} vs "
                        f"{old_tps[size]:.1f} tasks/s ({pct:+.1f}%)"
                    )
            # per-task dispatch overhead growing >20% is a control-plane
            # regression even when throughput survives (sleep-bound tasks
            # can hide it); sub-0.05ms values are sampling noise, not a
            # trend, so they never gate
            old_disp = old.get("dispatch") or {}
            for size, row in (cfg.get("dispatch") or {}).items():
                if not isinstance(row, dict):
                    continue
                ov = row.get("dispatch_overhead_ms")
                old_ov = (old_disp.get(size) or {}).get(
                    "dispatch_overhead_ms"
                )
                pct = _delta_pct(ov, old_ov)
                if (
                    pct is not None
                    and pct >= PERF_GATE_THRESHOLD_PCT
                    and isinstance(ov, (int, float))
                    and ov > 0.05
                ):
                    out.append(
                        f"fleet_scaling {size}w dispatch overhead "
                        f"{ov:.3f}ms/task vs {old_ov:.3f}ms/task "
                        f"({pct:+.1f}%)"
                    )
            continue
        if name == "scheduler_deepchain":
            pct = _delta_pct(cfg.get("speedup"), old.get("speedup"))
            if pct is not None and pct <= -PERF_GATE_THRESHOLD_PCT:
                out.append(
                    f"scheduler_deepchain speedup {cfg['speedup']:.2f}x vs "
                    f"{old['speedup']:.2f}x ({pct:+.1f}%)"
                )
            cur_df = (cfg.get("dataflow") or {}).get("elapsed")
            old_df = (old.get("dataflow") or {}).get("elapsed")
            pct = _delta_pct(cur_df, old_df)
            if pct is not None and pct >= PERF_GATE_THRESHOLD_PCT:
                out.append(
                    f"scheduler_deepchain dataflow wall {cur_df:.2f}s vs "
                    f"{old_df:.2f}s ({pct:+.1f}%)"
                )
            continue
        if name in ("p2p_transfer", "rechunk_shuffle"):
            # the data-plane wins must not rot: saved bytes dropping >20%
            # or the peer-enabled wall clock growing >20% both gate
            # (p2p_transfer is the deep elementwise chain; rechunk_shuffle
            # the transpose-heavy all-to-all — same record shape)
            pct = _delta_pct(
                cfg.get("saved_fraction"), old.get("saved_fraction")
            )
            if pct is not None and pct <= -PERF_GATE_THRESHOLD_PCT:
                out.append(
                    f"{name} saved_fraction "
                    f"{cfg['saved_fraction']:.2f} vs "
                    f"{old['saved_fraction']:.2f} ({pct:+.1f}%)"
                )
            cur_pe = (cfg.get("peer") or {}).get("elapsed")
            old_pe = (old.get("peer") or {}).get("elapsed")
            pct = _delta_pct(cur_pe, old_pe)
            if pct is not None and pct >= PERF_GATE_THRESHOLD_PCT:
                out.append(
                    f"{name} peer wall {cur_pe:.2f}s vs "
                    f"{old_pe:.2f}s ({pct:+.1f}%)"
                )
            continue
        if name == "overload_shedding":
            # the ladder's reason to exist: shed-on goodput must beat
            # shed-off in the SAME run, and must not rot run-over-run
            ratio = cfg.get("goodput_ratio")
            if isinstance(ratio, (int, float)) and ratio < 1.0:
                out.append(
                    f"overload_shedding ladder-on goodput no longer beats "
                    f"ladder-off (ratio {ratio:.2f}x)"
                )
            pct = _delta_pct(cfg.get("goodput_on"), old.get("goodput_on"))
            if pct is not None and pct <= -PERF_GATE_THRESHOLD_PCT:
                out.append(
                    f"overload_shedding goodput {cfg['goodput_on']:.2f}/s "
                    f"vs {old['goodput_on']:.2f}/s ({pct:+.1f}%)"
                )
            continue  # a paced, fixed-length scenario: wall is by design
        if name == "multitenant_service":
            # the front door must not rot: QPS dropping >20% or p99
            # latency growing >20% both gate (elapsed rides the generic
            # wall check below like every other config)
            pct = _delta_pct(cfg.get("qps"), old.get("qps"))
            if pct is not None and pct <= -PERF_GATE_THRESHOLD_PCT:
                out.append(
                    f"multitenant_service QPS {cfg['qps']:.1f} vs "
                    f"{old['qps']:.1f} ({pct:+.1f}%)"
                )
            pct = _delta_pct(cfg.get("p99_s"), old.get("p99_s"))
            if pct is not None and pct >= PERF_GATE_THRESHOLD_PCT:
                out.append(
                    f"multitenant_service p99 {cfg['p99_s']:.3f}s vs "
                    f"{old['p99_s']:.3f}s ({pct:+.1f}%)"
                )
            # per-tenant p99: one tenant's SLO rotting must gate even
            # when the other tenants keep the GLOBAL percentile flat
            old_tenants = old.get("tenants") or {}
            for tenant, row in (cfg.get("tenants") or {}).items():
                if not isinstance(row, dict):
                    continue
                old_p99 = (old_tenants.get(tenant) or {}).get("p99_s")
                pct = _delta_pct(row.get("p99_s"), old_p99)
                if pct is not None and pct >= PERF_GATE_THRESHOLD_PCT:
                    out.append(
                        f"multitenant_service {tenant} p99 "
                        f"{row['p99_s']:.3f}s vs {old_p99:.3f}s "
                        f"({pct:+.1f}%)"
                    )
        pct = _delta_pct(cfg.get("elapsed"), old.get("elapsed"))
        if pct is not None and pct >= PERF_GATE_THRESHOLD_PCT:
            out.append(
                f"{name} wall {cfg['elapsed']:.2f}s vs "
                f"{old['elapsed']:.2f}s ({pct:+.1f}%)"
            )
    return out


def _print_scheduler_deltas(cur: dict, old: dict, label: str) -> None:
    """Scheduler trajectory: deep-chain wall clock per mode plus the
    dataflow speedup, with a LOUD flag when the dataflow path stops
    beating the op barrier (>20 % speedup drop or wall-clock regression)
    — the number the chunk-granular scheduler is on the hook for."""
    op = (cur.get("oplevel") or {}).get("elapsed")
    df = (cur.get("dataflow") or {}).get("elapsed")
    speedup = cur.get("speedup")
    early = (cur.get("dataflow") or {}).get("tasks_dispatched_early", 0)
    print(
        f"trajectory scheduler_deepchain: oplevel {op:.2f}s, dataflow "
        f"{df:.2f}s, speedup {speedup:.2f}x, {early} task(s) dispatched "
        "early" if isinstance(op, (int, float)) and isinstance(
            df, (int, float)
        ) else "trajectory scheduler_deepchain: incomplete record",
        file=sys.stderr,
    )
    if isinstance(speedup, (int, float)) and speedup < 1.05:
        print(
            "SCHEDULER REGRESSION: dataflow no longer beats the op-level "
            f"barrier on the deep chain (speedup {speedup:.2f}x)",
            file=sys.stderr,
        )
    if not old:
        print("trajectory scheduler_deepchain: no prior record to compare "
              f"against in {label}" if label else
              "trajectory scheduler_deepchain: first record",
              file=sys.stderr)
        return
    # same rules (and threshold) as the tier-1 gate, via the shared helper
    regressed = [
        r for r in perf_regressions(
            {"configs": {"scheduler_deepchain": old}},
            {"configs": {"scheduler_deepchain": cur}},
        )
    ]
    if regressed:
        print(
            f"SCHEDULER REGRESSION (>{PERF_GATE_THRESHOLD_PCT:.0f}% vs "
            + (label or "prior record") + "): " + "; ".join(regressed),
            file=sys.stderr,
        )
    else:
        print(
            f"trajectory scheduler_deepchain: within "
            f"{PERF_GATE_THRESHOLD_PCT:.0f}% of {label}",
            file=sys.stderr,
        )


def _print_p2p_deltas(
    cur: dict, old: dict, label: str,
    name: str = "p2p_transfer", bar: float = 0.30,
) -> None:
    """Data-plane trajectory (the deep-chain ``p2p_transfer`` and the
    transpose-heavy ``rechunk_shuffle`` share a record shape): saved read
    bytes, hit rate, and per-mode wall clock, with a LOUD flag when the
    saved fraction falls under the config's acceptance bar (30% for the
    chain, 40% for the shuffle) or the shared gate rules flag a
    regression."""
    sf = cur.get("saved_fraction")
    hr = cur.get("hit_rate")
    so = (cur.get("store_only") or {}).get("elapsed")
    pe = (cur.get("peer") or {}).get("elapsed")
    if isinstance(sf, (int, float)) and isinstance(pe, (int, float)):
        print(
            f"trajectory {name}: saved_fraction {sf:.0%}, hit rate "
            f"{(hr or 0):.0%}, store-only {so:.2f}s vs peer {pe:.2f}s",
            file=sys.stderr,
        )
        if sf < bar:
            print(
                f"P2P REGRESSION: {name} store_read_bytes_saved fell under "
                f"the {bar:.0%} acceptance bar (saved_fraction {sf:.0%})",
                file=sys.stderr,
            )
    else:
        print(f"trajectory {name}: incomplete record", file=sys.stderr)
    if not old:
        print(f"trajectory {name}: no prior record to compare against "
              f"in {label}" if label else
              f"trajectory {name}: first record", file=sys.stderr)
        return
    regressed = perf_regressions(
        {"configs": {name: old}},
        {"configs": {name: cur}},
    )
    if regressed:
        print(
            f"P2P REGRESSION (>{PERF_GATE_THRESHOLD_PCT:.0f}% vs "
            + (label or "prior record") + "): " + "; ".join(regressed),
            file=sys.stderr,
        )
    else:
        print(
            f"trajectory {name}: within "
            f"{PERF_GATE_THRESHOLD_PCT:.0f}% of {label}",
            file=sys.stderr,
        )


def _print_multitenant_deltas(cur: dict, old: dict, label: str) -> None:
    """Multi-tenant service trajectory: QPS, latency quantiles, fairness,
    with a LOUD flag on the shared gate rules (QPS drop / p99 growth /
    wall regression) and on a fairness ratio leaving its bound."""
    qps = cur.get("qps")
    fr = cur.get("fairness_ratio")
    if isinstance(qps, (int, float)):
        print(
            f"trajectory multitenant_service: {qps:.1f} QPS, p50 "
            f"{(cur.get('p50_s') or 0) * 1000:.0f}ms, p99 "
            f"{(cur.get('p99_s') or 0) * 1000:.0f}ms, fairness "
            f"{(fr or 0):.2f}, {cur.get('result_cache_hits', 0)} "
            "result-cache hit(s)",
            file=sys.stderr,
        )
        if isinstance(fr, (int, float)) and fr > 2.0:
            print(
                "SERVICE FAIRNESS REGRESSION: max/min per-tenant "
                f"throughput ratio {fr:.2f} exceeds the 2.0 bound for "
                "equal-weight tenants",
                file=sys.stderr,
            )
    else:
        print("trajectory multitenant_service: incomplete record",
              file=sys.stderr)
    if not old:
        print("trajectory multitenant_service: no prior record to compare "
              f"against in {label}" if label else
              "trajectory multitenant_service: first record",
              file=sys.stderr)
        return
    regressed = perf_regressions(
        {"configs": {"multitenant_service": old}},
        {"configs": {"multitenant_service": cur}},
    )
    if regressed:
        print(
            f"SERVICE REGRESSION (>{PERF_GATE_THRESHOLD_PCT:.0f}% vs "
            + (label or "prior record") + "): " + "; ".join(regressed),
            file=sys.stderr,
        )
    else:
        print(
            f"trajectory multitenant_service: within "
            f"{PERF_GATE_THRESHOLD_PCT:.0f}% of {label}",
            file=sys.stderr,
        )


def _print_trajectory_deltas(metrics_record: dict, prev_trajectory) -> None:
    """One line per config vs the previous trajectory (stderr — stdout's
    last line belongs to the driver), so the bench history stops being
    write-only: a wall-clock or peak-RSS regression is visible in the run
    output itself, without anyone diffing JSON files."""
    prev, label = prev_trajectory
    if not prev:
        print("trajectory: no previous bench record to compare against",
              file=sys.stderr)
        return
    for metric, cur in metrics_record.items():
        old = prev.get(metric)
        if metric == "fleet_scaling":
            _print_scaling_deltas(cur, old if isinstance(old, dict) else {},
                                  label)
            continue
        if metric == "scheduler_deepchain":
            _print_scheduler_deltas(
                cur, old if isinstance(old, dict) else {}, label
            )
            continue
        if metric == "p2p_transfer":
            _print_p2p_deltas(cur, old if isinstance(old, dict) else {},
                              label)
            continue
        if metric == "rechunk_shuffle":
            _print_p2p_deltas(cur, old if isinstance(old, dict) else {},
                              label, name="rechunk_shuffle", bar=0.40)
            continue
        if metric == "multitenant_service":
            _print_multitenant_deltas(
                cur, old if isinstance(old, dict) else {}, label
            )
            continue
        if not isinstance(old, dict):
            print(f"trajectory {metric}: new config (no prior record in "
                  f"{label})", file=sys.stderr)
            continue
        parts = []
        for key, name, fmt in (
            ("elapsed", "wall", "{:.2f}s"),
            ("worker_rss_peak", "peak-rss", "{:.0f}B"),
            ("value", "throughput", "{:.3f}"),
        ):
            pct = _delta_pct(cur.get(key), old.get(key))
            if pct is None:
                continue
            # wall clock / RSS: up is worse; throughput: up is better
            worse = pct > 0 if key != "value" else pct < 0
            tag = "regressed" if abs(pct) >= 5 and worse else (
                "improved" if abs(pct) >= 5 else "~flat")
            parts.append(
                f"{name} {fmt.format(cur[key])} vs {fmt.format(old[key])} "
                f"({pct:+.1f}%, {tag})"
            )
        if parts:
            print(f"trajectory {metric}: " + "; ".join(parts) +
                  f"  [vs {label}]", file=sys.stderr)


if __name__ == "__main__":
    main()
