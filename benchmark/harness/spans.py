"""The span recorder of the traced run.

The program's executor emits no span of its own yet, so the benchmark wraps
calls into each layer from outside. A per-layer metric's reader file names
the callables it needs as ``module:qualified.name``; ``Recorder.wrap`` puts a
timing wrapper around each for the length of the run and ``unwrap`` takes it
off again. Each call becomes one span (name, start, end, parent, the index of
the compute it belongs to) on the host's monotonic clock, and is also written
into the profiler's trace with ``jax.profiler.TraceAnnotation`` so that an
idle gap on the device has a host span to blame. A callable that is no longer
there is reported and skipped: its metric is then absent, the run goes on.

Nothing here runs with ``--trace 0``: no ``Recorder`` is made."""

from __future__ import annotations

import contextlib
import importlib
import time
from dataclasses import dataclass
from typing import Optional

#: what every annotation this recorder writes into the profiler's trace
#: starts with, so that the reduction finds them again
ANNOTATION_PREFIX = "bench:"


@dataclass
class Span:
    name: str
    start: float
    end: float
    parent: Optional[int]  # index into Recorder.spans
    compute: int  # index of the compute it belongs to, -1 outside one

    @property
    def seconds(self) -> float:
        return self.end - self.start


def resolve(target: str):
    """``module:Qual.name`` -> (owner object, attribute name, callable)."""
    module_name, _, qualname = target.partition(":")
    owner = importlib.import_module(module_name)
    *path, attr = qualname.split(".")
    for part in path:
        owner = getattr(owner, part)
    return owner, attr, getattr(owner, attr)


class Recorder:
    def __init__(self):
        self.spans: list[Span] = []
        self.missing: list[str] = []
        self._stack: list[int] = []
        self._compute = -1
        self._wrapped: list[tuple] = []

    # -- recording ---------------------------------------------------------

    def open(self, name: str) -> tuple:
        """Start a span; returns the handle that ``close`` takes."""
        import jax.profiler

        index = len(self.spans)
        parent = self._stack[-1] if self._stack else None
        annotation = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
        annotation.__enter__()
        self.spans.append(Span(name, time.perf_counter(), float("nan"), parent,
                               self._compute))
        self._stack.append(index)
        return index, annotation

    def close(self, handle: tuple) -> None:
        index, annotation = handle
        self.spans[index].end = time.perf_counter()
        annotation.__exit__(None, None, None)
        self._stack.pop()

    @contextlib.contextmanager
    def compute(self, index: int):
        """The root span of compute ``index``."""
        self._compute = index
        handle = self.open("compute")
        try:
            yield
        finally:
            self.close(handle)
            self._compute = -1

    # -- wrapping ----------------------------------------------------------

    def wrap(self, target: str, ready_first: bool = False) -> bool:
        """Record every call of ``target`` as a span named by its qualified
        name. With ``ready_first`` the wrapper first waits for the call's
        first argument to be ready on the device and records that wait as a
        span of its own, ``<name>.ready``: a fetch would otherwise absorb
        the whole device execution that produces its value."""
        if any(t == target for t, *_ in self._wrapped):
            return True
        try:
            owner, attr, original = resolve(target)
        except (ImportError, AttributeError) as e:
            self.missing.append(target)
            print(f"WARNING span target {target} is not there ({e}); "
                  "metrics that read it will be absent", flush=True)
            return False
        name = target.partition(":")[2]
        recorder = self

        def wrapper(*args, **kwargs):
            if ready_first:
                import jax

                handle = recorder.open(name + ".ready")
                try:
                    # args[0] is self; the value is the first real argument
                    jax.block_until_ready(args[1] if len(args) > 1 else kwargs)
                finally:
                    recorder.close(handle)
            handle = recorder.open(name)
            try:
                return original(*args, **kwargs)
            finally:
                recorder.close(handle)

        wrapper.__wrapped__ = original
        wrapper.__name__ = getattr(original, "__name__", attr)
        setattr(owner, attr, wrapper)
        self._wrapped.append((target, owner, attr, original))
        return True

    def unwrap(self) -> None:
        while self._wrapped:
            _, owner, attr, original = self._wrapped.pop()
            setattr(owner, attr, original)

    # -- reading -----------------------------------------------------------

    def of(self, name: str, compute: int) -> list[Span]:
        return [s for s in self.spans if s.name == name and s.compute == compute]

    def children_seconds(self, span: Span) -> float:
        me = self.spans.index(span)
        return sum(s.seconds for s in self.spans if s.parent == me)

    def self_seconds(self, span: Span) -> float:
        """A span's duration minus what its child spans cover."""
        return span.seconds - self.children_seconds(span)
