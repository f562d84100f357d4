"""The program's own spans and counters, for the readers of the per-layer
metrics that read them.

The device executor times its phases as spans of the program's task-span
pipeline (``jax.preload``, ``jax.h2d``, ``jax.dispatch``, ``jax.d2h``, the
store's ``storage_read``, ``chunk_encode``, ``fsync`` ...) and reports their
totals by name in a compute's ``executor_stats`` as ``span_s`` (seconds),
``span_self_s`` (seconds less what child spans cover) and ``span_n`` (calls),
with ``spans_dropped`` beside them. The program records spans only when it is
asked to. The harness loads reader files in a traced run only, before the
first compute, so a reader that wants the spans calls ``arm()`` where it is
loaded: that sets the program's documented operator override for the rest of
the process, which is one run of one cell. An untraced run loads no reader
and arms nothing. (A test that rehearses a traced run in a longer-lived
process restores the variable itself.)

What the readers get is what ``harness.loop.Traced.stats`` holds: the
counters of the window's last compute, not a median over the window. A
program without these spans or counters (the parent of the PR that brought
them) gives ``None`` everywhere, and the metric is left out of the line."""

from __future__ import annotations

import os
from typing import Optional

#: ``cubed_tpu.observability.accounting.SPANS_ENV_VAR``, by value: were the
#: program to rename it, these metrics fall silent and nothing fails
SPANS_ENV_VAR = "CUBED_TPU_TASK_SPANS"


def arm() -> None:
    """Have the program record its spans from here on."""
    os.environ[SPANS_ENV_VAR] = "1"


def span_seconds(traced, *names: str, self_time: bool = False) -> Optional[float]:
    """Seconds the last compute spent in the spans called ``names``, summed
    (their self time with ``self_time``). None where the program reported no
    span totals, dropped a span (the totals are then short), or recorded
    none of the names."""
    stats = traced.stats
    table = stats.get("span_self_s" if self_time else "span_s")
    if not table or stats.get("spans_dropped"):
        return None
    found = [table[name] for name in names if name in table]
    return sum(found) if found else None


def child_seconds(traced, name: str) -> Optional[float]:
    """Seconds that the spans called ``name`` spent in the spans directly
    inside them: their duration less their self time."""
    whole = span_seconds(traced, name)
    own = span_seconds(traced, name, self_time=True)
    return None if whole is None or own is None else whole - own
