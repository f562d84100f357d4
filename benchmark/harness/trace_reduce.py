"""From the profiler's device trace to busy and idle time, per-chip shares,
collective time, the operations that took most time and the idle gaps by
what the host was doing in them.

The reduction works on a plain form of the trace,

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

which ``load_xplane`` takes from the ``.xplane.pb`` that ``jax.profiler``
writes (read with ``jax.profiler.ProfileData``, nothing but jax) and which a
test can write by hand (``benchmark/tests/data/``). Device planes are the ones
named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event for each
operation that ran (``tokamax`` and the trace viewer read the same line). Host spans are the ``bench:`` annotations the span
recorder wrote on the host plane; ``bench:compute`` marks each traced compute,
and everything is measured inside those: busy time is the union of the
operations' intervals clipped to the computes, the window is the computes'
total length, idle is the rest."""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"/device:(?:TPU|GPU):(\d+)")
#: the line of a device plane that holds its operations (a v5e trace has "XLA
#: Modules", "XLA Ops" and "Async XLA Ops"; my chip run, PR 24); where no line
#: is called so, every line that is not a summary of another (steps, modules,
#: name scopes, source lines) nor the asynchronous copies is taken
OPS_LINE = "XLA Ops"
SUMMARY_LINE = re.compile(r"step|module|traceme|scope|source|framework|async", re.IGNORECASE)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast",
    re.IGNORECASE,
)
NS = 1e-9


# ---------------------------------------------------------------------------
# interval arithmetic, on (start, end) pairs
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint intervals that cover what ``intervals`` cover."""
    merged: list = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [tuple(i) for i in merged]


def total(intervals) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals, windows) -> list:
    """The parts of (disjoint, sorted) ``intervals`` inside ``windows``."""
    out = []
    for ws, we in windows:
        for start, end in intervals:
            lo, hi = max(start, ws), min(end, we)
            if hi > lo:
                out.append((lo, hi))
    return out


def subtract(windows, intervals) -> list:
    """The parts of ``windows`` that (disjoint, sorted) ``intervals`` leave."""
    gaps = []
    for ws, we in windows:
        at = ws
        for start, end in intervals:
            if end <= at or start >= we:
                continue
            if start > at:
                gaps.append((at, start))
            at = max(at, end)
        if at < we:
            gaps.append((at, we))
    return gaps


def self_times(events) -> dict:
    """{name: seconds} of each event's own time: its duration minus that of
    the events nested inside it on the same line (a ``while`` holds its body's
    operations), summed by name."""
    out: dict = defaultdict(float)
    stack: list = []  # [name, end, own]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] += done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, end, dur])
    for done in stack:
        out[done[0]] += done[2]
    return {k: v * NS for k, v in out.items()}


def innermost(spans, start: float, end: float) -> list:
    """Cut [start, end) at every boundary of ``spans`` ((name, s, e)) and
    name each piece after the shortest span that covers it: what the host
    was doing there, as closely as its spans say. Pieces no span covers are
    named ``(no host span)``."""
    cuts = sorted({start, end, *(t for _, s, e in spans for t in (s, e)
                                 if start < t < end)})
    pieces = []
    for lo, hi in zip(cuts, cuts[1:]):
        covering = [(e - s, name) for name, s, e in spans if s <= lo and e >= hi]
        pieces.append((min(covering)[1] if covering else "(no host span)", lo, hi))
    return pieces


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An operation's name as the trace has it is its whole HLO instruction
    (``%fusion.8 = (f32[...]) fusion(...)``): keep what stands before the
    ``=``, so that a name is a name and an operand called ``%all-reduce.3``
    does not make a fusion a collective."""
    return name.split(" = ", 1)[0].lstrip("%")[:100]


def load_xplane(path: str, prefix: str) -> dict:
    """The plain form of one ``.xplane.pb``: every line of the device planes,
    and of every other plane only the events whose name starts with
    ``prefix`` (the recorder's annotations)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            events = [
                [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(prefix)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict) -> str:
    """One line for a person: the planes, their lines with the number of
    events, and the milliseconds each line spans on the trace's clock."""
    def line(ln):
        first = min(e[1] for e in ln["events"]) / 1e6
        last = max(e[1] + e[2] for e in ln["events"]) / 1e6
        return f"{ln['name']}({len(ln['events'])} events, {first:.1f}-{last:.1f} ms)"

    return "; ".join(
        f"{p['name']}: " + ", ".join(map(line, p["lines"])) for p in trace["planes"]
    )


def reduce_trace(trace: dict, prefix: str, top: int = 10) -> dict:
    """Busy, idle, shares, collectives, top operations and idle gaps.

    Returns a dict with: ``computes`` (how many ``<prefix>compute`` spans the
    trace holds), ``window_s`` (their total length), ``busy_s`` ({chip: busy
    seconds inside the computes}), ``collective_s`` ({chip: seconds}),
    ``device_ops`` and ``idle_gaps`` (lists of [name, seconds], longest
    first, of the busiest chip), or None where the trace holds no compute
    span or no device plane."""
    host = []  # (name without prefix, start, end)
    devices = {}  # chip -> events of its operations line
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m is None:
            for line in plane["lines"]:
                host += [
                    (name[len(prefix):], start, start + dur)
                    for name, start, dur in line["events"]
                    if name.startswith(prefix)
                ]
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE] or [
            ln for ln in plane["lines"] if not SUMMARY_LINE.search(ln["name"])
        ]
        devices.setdefault(int(m.group(1)), []).extend(
            e for ln in lines for e in ln["events"]
        )
    devices = {chip: events for chip, events in devices.items() if events}
    windows = union((s, e) for name, s, e in host if name == "compute")
    if not windows or not devices:
        return None
    n_computes = sum(1 for name, _, _ in host if name == "compute")
    busy, collective = {}, {}
    for chip, events in devices.items():
        covered = union((s, s + d) for _, s, d in events)
        busy[chip] = clip(covered, windows)
        collective[chip] = clip(
            union((s, s + d) for name, s, d in events if COLLECTIVE.search(name)),
            windows,
        )
    busy_s = {chip: total(iv) * NS for chip, iv in busy.items()}
    if not any(busy_s.values()):
        return None
    busiest = max(busy_s, key=busy_s.get)

    inside = [
        e for e in devices[busiest]
        if any(e[1] < we and e[1] + e[2] > ws for ws, we in windows)
    ]
    ops = sorted(self_times(inside).items(), key=lambda kv: -kv[1])[:top]

    gaps: dict = defaultdict(float)
    for gs, ge in subtract(windows, busy[busiest]):
        for name, lo, hi in innermost(host, gs, ge):
            gaps[name] += (hi - lo) * NS
    return {
        "computes": n_computes,
        "window_s": total(windows) * NS,
        "busy_s": busy_s,
        "collective_s": {chip: total(iv) * NS for chip, iv in collective.items()},
        "busiest": busiest,
        "device_ops": [[name, s] for name, s in ops],
        "idle_gaps": [
            [name, s] for name, s in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        ],
    }
