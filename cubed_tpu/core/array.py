"""CoreArray: the chunked-array handle tying together a name, a (possibly lazy)
Zarr target, a Spec, and a Plan.

Reference parity: cubed/core/array.py (behavioral; clean-room).
"""

from __future__ import annotations

from math import prod
from operator import mul
from typing import Optional, Sequence, TypeVar

import numpy as np

from ..chunks import blockdims_from_blockshape
from ..runtime.types import Callback
from ..spec import Spec, spec_from_config
from ..storage.zarr import LazyZarrArray, open_if_lazy_zarr_array
from ..utils import chunk_memory, memory_repr, to_chunksize

T_ChunkedArray = TypeVar("T_ChunkedArray", bound="CoreArray")


class CoreArray:
    """A chunked n-dimensional array handle participating in a lazy plan."""

    def __init__(self, name: str, zarray_maybe_lazy, spec: Spec, plan):
        self.name = name
        self.zarray_maybe_lazy = zarray_maybe_lazy
        self.spec = spec
        self.plan = plan

    # -- metadata ----------------------------------------------------------

    @property
    def chunkmem(self) -> int:
        """Bytes of one chunk of this array."""
        return chunk_memory(self.dtype, self.chunksize)

    @property
    def chunks(self) -> tuple[tuple[int, ...], ...]:
        return blockdims_from_blockshape(self.shape, self.zarray_maybe_lazy.chunks)

    @property
    def chunksize(self) -> tuple[int, ...]:
        return tuple(self.zarray_maybe_lazy.chunks)

    @property
    def dtype(self):
        return self.zarray_maybe_lazy.dtype

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def numblocks(self) -> tuple[int, ...]:
        return tuple(len(c) for c in self.chunks)

    @property
    def npartitions(self) -> int:
        return prod(self.numblocks) if self.shape else 1

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.zarray_maybe_lazy.shape)

    @property
    def size(self) -> int:
        return prod(self.shape) if self.shape else 1

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    @property
    def itemsize(self) -> int:
        return self.dtype.itemsize

    @property
    def zarray(self):
        """The concrete storage array (opens a lazy target)."""
        return open_if_lazy_zarr_array(self.zarray_maybe_lazy)

    # -- compute -----------------------------------------------------------

    def compute(self, **kwargs):
        """Execute the plan for this array and return it as a numpy array."""
        result = compute(self, **kwargs)
        return result[0] if result else None

    def _read_stored(self) -> np.ndarray:
        arr = self.zarray
        out = arr[...] if self.shape else arr[()]
        return np.asarray(out)

    def rechunk(self, chunks, **kwargs):
        from .ops import rechunk

        return rechunk(self, chunks, **kwargs)

    def visualize(self, *args, **kwargs):
        return self.plan.visualize(*args, **kwargs)

    def explain(self, **kwargs):
        """EXPLAIN the plan that computes this array (``Plan.explain``),
        defaulting the spec and target array name to this array's."""
        kwargs.setdefault("spec", self.spec)
        kwargs.setdefault("array_names", (self.name,))
        return self.plan.explain(**kwargs)

    def __getitem__(self, key):
        from .ops import index

        return index(self, key)

    def __repr__(self) -> str:
        return f"cubed_tpu.CoreArray<{self.name}, shape={self.shape}, dtype={self.dtype}, chunks={self.chunks}>"


def check_array_specs(arrays: Sequence) -> Optional[Spec]:
    """All arrays in one computation must share an equivalent Spec."""
    specs = [a.spec for a in arrays if hasattr(a, "spec")]
    if not specs:
        return None
    first = specs[0]
    for other in specs[1:]:
        if other != first:
            raise ValueError(
                f"Arrays must have same spec in single computation. "
                f"Specs: {first!r} and {other!r}"
            )
    return first


def execute(
    *arrays,
    executor=None,
    callbacks: Optional[Sequence[Callback]] = None,
    optimize_graph: bool = True,
    optimize_function=None,
    resume: Optional[bool] = None,
    **kwargs,
) -> None:
    """Run the arrays' combined plan to the end and read nothing back: when
    this returns every array is in its store. ``compute`` adds the read;
    ``to_zarr`` and ``store`` (core/ops.py) stop here."""
    from .plan import arrays_to_plan

    if not arrays:
        return
    spec = check_array_specs(arrays)
    plan = arrays_to_plan(*arrays)
    if executor is None:
        executor = spec.executor if spec is not None else None
    if executor is None:
        from ..runtime.executors.python import PythonDagExecutor

        executor = PythonDagExecutor()
    plan.execute(
        executor=executor,
        callbacks=callbacks,
        optimize_graph=optimize_graph,
        optimize_function=optimize_function,
        resume=resume,
        array_names=tuple(a.name for a in arrays),
        spec=spec,
        **kwargs,
    )


def compute(*arrays, **kwargs) -> list[np.ndarray]:
    """Compute multiple arrays in one plan execution; return numpy results.

    The whole of every array is read from its store into the client's
    memory: the call for results small enough to hold. To compute into a
    store without reading it back, use ``to_zarr`` or ``store``. Keyword
    arguments are ``execute``'s."""
    execute(*arrays, **kwargs)
    return [a._read_stored() for a in arrays]


def visualize(*arrays, filename="cubed", format=None, **kwargs):
    """Produce a visualization of the combined plan of the given arrays."""
    from .plan import arrays_to_plan

    plan = arrays_to_plan(*arrays)
    return plan.visualize(filename=filename, format=format, **kwargs)


def measure_reserved_mem(executor=None, work_dir: Optional[str] = None, **kwargs) -> int:
    """Measure memory used by the runtime before any task data is loaded.

    Runs a trivial computation and reports the worker's peak measured memory,
    for use as ``reserved_mem``. Reference parity: cubed/core/array.py:343-388.
    """
    from ..array_api.creation_functions import ones
    from ..extensions.history import HistoryCallback

    a = ones((1,), chunks=(1,), spec=Spec(work_dir=work_dir, allowed_mem="100MB"))
    history = HistoryCallback()
    a.compute(executor=executor, callbacks=[history], **kwargs)
    events = history.events
    if events:
        peaks = [
            e.peak_measured_mem_start
            for e in events
            if e.peak_measured_mem_start is not None
        ]
        if peaks:
            return max(peaks)
    from ..utils import peak_measured_mem

    return peak_measured_mem()
