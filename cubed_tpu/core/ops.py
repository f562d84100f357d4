"""Whole-array operations, all built on the two primitives (blockwise, rechunk).

Reference parity: cubed/core/ops.py (behavioral; clean-room). Reduction uses
the tree formulation (reference ``reduction_new``, core/ops.py:906-1090) as the
default — it maps directly onto collective trees on the TPU executor.
"""

from __future__ import annotations

import itertools
import math
from functools import partial
from numbers import Integral, Number
from typing import Any, Callable, Optional, Sequence, Union

import numpy as np

from ..backend_array_api import numpy_array_to_backend_array, nxp
from ..chunks import (
    blockdims_from_blockshape,
    broadcast_chunks,
    common_blockdim,
    normalize_chunks,
    numblocks as chunks_to_numblocks,
)
from ..primitive.blockwise import (
    blockwise as primitive_blockwise,
    general_blockwise as primitive_general_blockwise,
)
from ..primitive.rechunk import rechunk as primitive_rechunk
from ..spec import Spec, spec_from_config
from ..storage.store import ZarrV2Array, open_zarr_array
from ..storage.virtual import (
    virtual_empty,
    virtual_full,
    virtual_in_memory,
    virtual_offsets,
)
from ..utils import (
    chunk_memory,
    get_item,
    offset_to_block_id,
    to_chunksize,
)
from .array import CoreArray, check_array_specs, execute
from .plan import Plan, gensym, new_temp_path


# ---------------------------------------------------------------------------
# Creation from / export to storage
# ---------------------------------------------------------------------------


def _spec_of(*arrays, spec=None) -> Spec:
    if spec is not None:
        return spec
    found = check_array_specs([a for a in arrays if isinstance(a, CoreArray)])
    return found if found is not None else spec_from_config(None)


def new_array(name, target, spec, plan) -> "CoreArray":
    from ..array_api.array_object import Array

    return Array(name, target, spec, plan)


def from_array(x, chunks="auto", asarray=None, spec=None) -> "CoreArray":
    """Create an array from an in-memory (numpy/jax) or zarr-like array.

    Zarr-like stores wrap in place (no data read); small in-memory arrays ride
    the plan as virtual arrays; larger ones are sliced per output chunk by a
    map_blocks whose closure carries the source (reference cubed/core/ops.py:40-85).
    """
    if isinstance(x, CoreArray):
        raise ValueError(
            "Array is already a cubed_tpu array - use rechunk instead of from_array"
        )
    spec = spec_from_config(spec)
    if isinstance(x, ZarrV2Array):
        name = gensym("from-array")
        plan = Plan._new(name, "from_array", x)
        arr = new_array(name, x, spec, plan)
        outchunks = normalize_chunks(chunks, x.shape, dtype=x.dtype)
        if to_chunksize(outchunks) != tuple(x.chunks):
            arr = rechunk(arr, outchunks)
        return arr
    x = np.asarray(x)
    outchunks = normalize_chunks(chunks, x.shape, dtype=x.dtype)
    name = gensym("array")
    from ..storage.virtual import MAX_IN_MEMORY_BYTES

    if x.nbytes <= MAX_IN_MEMORY_BYTES:
        target = virtual_in_memory(x, to_chunksize(outchunks) if x.shape else ())
        plan = Plan._new(name, "from_array", target)
        return new_array(name, target, spec, plan)

    # large in-memory source: slice it per output chunk inside the task
    def _from_array_chunk(chunk, block_id=None):
        sel = get_item(outchunks, block_id)
        return numpy_array_to_backend_array(x[sel])

    _from_array_chunk.__name__ = "from_array"
    _from_array_chunk.host_data_nbytes = x.nbytes
    return map_blocks(
        _from_array_chunk,
        empty_virtual_array(x.shape, dtype=x.dtype, chunks=outchunks, spec=spec),
        dtype=x.dtype,
    )


def from_zarr(store, path=None, spec=None, storage_options=None) -> "CoreArray":
    """Load an array from existing Zarr storage (lazily; no data read)."""
    spec = spec_from_config(spec)
    name = gensym("from-zarr")
    target = open_zarr_array(
        store if path is None else f"{store}/{path}",
        mode="r",
        storage_options=storage_options or (spec.storage_options if spec else None),
    )
    plan = Plan._new(name, "from_zarr", target)
    return new_array(name, target, spec, plan)


def to_zarr(
    x: CoreArray,
    store,
    path=None,
    executor=None,
    storage_options=None,
    compressor=None,
    **kwargs,
) -> None:
    """Compute the array and write it to a new Zarr store (eagerly).

    Computes for the side effect and returns ``None``: when it returns every
    chunk of the target is durable (written by atomic rename, its checksum
    in the manifest where the integrity mode keeps one), and nothing of the
    target has been read back, so the client never holds more than the
    plan's tasks do. ``compute()`` is the call that returns values.

    ``compressor`` is a Zarr v2 compressor config (e.g.
    ``{"id": "zlib", "level": 1}``; stdlib codecs zlib/gzip/bz2/lzma). The
    target metadata is stamped up front, so every chunk write — any
    executor, any worker — round-trips through the codec (the lazy target
    creation opens existing metadata rather than clobbering it,
    reference cubed/core/plan.py:430-432 semantics).
    """
    target = str(store) if path is None else f"{store}/{path}"
    if compressor is not None:
        open_zarr_array(
            target,
            mode="w",
            shape=x.shape,
            dtype=x.dtype,
            chunks=x.chunksize if x.ndim else (),
            storage_options=storage_options,
            compressor=compressor,
        )
    execute(_store_op(x, target, storage_options), executor=executor, **kwargs)


def store(sources, targets, executor=None, **kwargs) -> None:
    """Compute multiple arrays into multiple existing stores, in one plan
    execution. Like ``to_zarr`` it returns ``None`` once the targets are
    durable and reads none of them back."""
    if isinstance(sources, CoreArray):
        sources = [sources]
        targets = [targets]
    outs = [_store_op(s, t, None) for s, t in zip(sources, targets)]
    execute(*outs, executor=executor, **kwargs)


def _store_op(x: CoreArray, store, storage_options) -> CoreArray:
    def _identity(a):
        return a

    # values pass through untouched: a device executor may move them in
    # whatever representation keeps every bit (executors/jax.py)
    _identity.bit_preserving = True

    # identity blockwise into an explicit target store; fuses with producers
    return blockwise(
        _identity,
        tuple(range(x.ndim))[::-1],
        x,
        tuple(range(x.ndim))[::-1],
        dtype=x.dtype,
        target_store=str(store),
        storage_options=storage_options,
        shape_invariant=True,
    )


# ---------------------------------------------------------------------------
# Blockwise (core wrapper)
# ---------------------------------------------------------------------------


def blockwise(
    func: Callable,
    out_ind: Sequence,
    *args,  # pairs of (array, indices)
    dtype=None,
    adjust_chunks: Optional[dict] = None,
    new_axes: Optional[dict] = None,
    align_arrays: bool = True,
    target_store=None,
    storage_options=None,
    extra_projected_mem: int = 0,
    fusable: bool = True,
    extra_func_kwargs: Optional[dict] = None,
    **kwargs,
) -> CoreArray:
    arrays = list(args[0::2])
    inds = [tuple(i) if i is not None else None for i in args[1::2]]

    spec = _spec_of(*arrays)
    if align_arrays:
        _, arrays = unify_chunks(*itertools.chain(*zip(arrays, inds)))

    # chunking of each index symbol (max-blocks rule over aligned inputs;
    # ties break toward the larger extent — a size-1 dim BROADCASTS
    # against the symbol and must not define the output chunking)
    chunkss: dict = {}
    for a, ind in zip(arrays, inds):
        if ind is None:
            continue
        for sym, c in zip(ind, a.chunks):
            prev = chunkss.get(sym)
            if (
                prev is None
                or len(c) > len(prev)
                or (len(c) == len(prev) and sum(c) > sum(prev))
            ):
                chunkss[sym] = c
    if new_axes:
        for sym, size in new_axes.items():
            if isinstance(size, (tuple, list)):
                chunkss[sym] = tuple(size)
            else:
                chunkss[sym] = (size,)

    chunks_out = []
    for sym in out_ind:
        c = chunkss[sym]
        if adjust_chunks and sym in adjust_chunks:
            adj = adjust_chunks[sym]
            if callable(adj):
                c = tuple(adj(x) for x in c)
            elif isinstance(adj, (int, np.integer)):
                c = (int(adj),) * len(c)
            else:
                c = tuple(adj)
        chunks_out.append(tuple(c))
    chunks_out = tuple(chunks_out)
    shape = tuple(sum(c) for c in chunks_out)

    # multi-output (list-valued dtype): one op writes N arrays on the same
    # block grid — func returns a tuple per task (used by apply_gufunc's
    # multiple outputs); shapes/chunks are shared, dtypes per output
    multi, names, target_store = _alloc_output_names_stores(
        dtype, target_store, spec
    )
    out_name_arg = names if multi else names[0]
    shape_arg = [shape] * len(dtype) if multi else shape
    in_names = [a.name for a in arrays]

    prim_args = []
    for a, ind in zip(arrays, inds):
        prim_args.extend([a.zarray_maybe_lazy, ind])

    op = primitive_blockwise(
        func,
        tuple(out_ind),
        *prim_args,
        allowed_mem=spec.allowed_mem,
        reserved_mem=spec.reserved_mem,
        target_store=target_store,
        storage_options=storage_options or spec.storage_options,
        shape=shape_arg,
        dtype=dtype,
        chunks=chunks_out,
        new_axes=new_axes,
        in_names=in_names,
        out_name=out_name_arg,
        extra_projected_mem=extra_projected_mem,
        extra_func_kwargs=extra_func_kwargs,
        fusable=fusable,
        **kwargs,
    )
    op_label = func.__name__ if hasattr(func, "__name__") else "blockwise"
    return _wrap_op_outputs(op, op_label, spec, arrays, names)


def general_blockwise(
    func: Callable,
    block_function: Callable,
    *arrays,
    shape,
    dtype,
    chunks,
    extra_projected_mem: int = 0,
    num_input_blocks=None,
    fusable: bool = True,
    target_store=None,
    op_name: str = "general_blockwise",
    **kwargs,
):
    """Apply an explicit block function.

    Multi-output: pass ``dtype`` as a list (and optionally ``shape`` as a
    list of shapes, ``target_store`` as a list) — ``func`` then returns a
    tuple of arrays per task and a tuple of CoreArrays is returned, all
    produced by ONE op (reference analogue:
    cubed/primitive/blockwise.py:78-82 structured writes; promoted here to
    real multiple array targets priced once at plan time)."""
    spec = _spec_of(*arrays)
    multi, names, target_store = _alloc_output_names_stores(
        dtype, target_store, spec
    )
    if multi:
        shapes = (
            list(shape)
            if shape and isinstance(shape[0], (list, tuple))
            else [tuple(shape)] * len(dtype)
        )
        if isinstance(chunks, list):
            # per-output chunk sizes (same numblocks enforced by the
            # primitive), each normalized against its own shape/dtype
            if len(chunks) != len(dtype):
                raise ValueError(
                    "per-output chunks list must have one entry per "
                    f"output; got {len(chunks)} for {len(dtype)} outputs"
                )
            chunks = [
                normalize_chunks(c, s, dtype=dt)
                for c, s, dt in zip(chunks, shapes, dtype)
            ]
        else:
            chunks = normalize_chunks(chunks, shapes[0], dtype=dtype[0])
        out_name = names
        shape_arg = [tuple(s) for s in shapes]
    else:
        chunks = normalize_chunks(chunks, shape, dtype=dtype)
        out_name = names[0]
        shape_arg = tuple(shape)
    op = primitive_general_blockwise(
        func,
        block_function,
        *[a.zarray_maybe_lazy for a in arrays],
        allowed_mem=spec.allowed_mem,
        reserved_mem=spec.reserved_mem,
        target_store=target_store,
        storage_options=spec.storage_options,
        shape=shape_arg,
        dtype=dtype,
        chunks=chunks,
        in_names=[a.name for a in arrays],
        out_name=out_name,
        extra_projected_mem=extra_projected_mem,
        num_input_blocks=num_input_blocks,
        fusable=fusable,
    )
    return _wrap_op_outputs(op, op_name, spec, arrays, names)


def _alloc_output_names_stores(dtype, target_store, spec):
    """(multi?, output names, target store(s)) for an op's output(s).

    Multi-output (list-valued ``dtype``) requires a list target_store (one
    per output) or None (temp paths); a plain string would be silently
    iterated into per-character paths."""
    multi = isinstance(dtype, (list, tuple))
    if multi:
        names = [gensym("array") for _ in dtype]
        if target_store is None:
            target_store = [new_temp_path(n, spec) for n in names]
        elif isinstance(target_store, str):
            raise TypeError(
                "multi-output ops require target_store to be a list (one "
                "store per output) or None"
            )
    else:
        names = [gensym("array")]
        if target_store is None:
            target_store = new_temp_path(names[0], spec)
    return multi, names, target_store


def _wrap_op_outputs(op, op_label: str, spec, arrays, names):
    """Plan node(s) + CoreArray(s) for a finished primitive op: a tuple for
    multi-output ops, a single array otherwise."""
    if op.target_arrays is not None:
        targets = op.target_arrays
        plan = Plan._new(names, op_label, targets, op, False, *arrays)
        return tuple(
            new_array(n, t, spec, plan) for n, t in zip(names, targets)
        )
    plan = Plan._new(names[0], op_label, op.target_array, op, False, *arrays)
    return new_array(names[0], op.target_array, spec, plan)


# ---------------------------------------------------------------------------
# Elementwise and map operations
# ---------------------------------------------------------------------------


def elemwise(func: Callable, *args: CoreArray, dtype=None) -> CoreArray:
    """Apply an elementwise function with broadcasting."""
    if dtype is None:
        raise ValueError("dtype must be specified for elemwise")
    shapes = [getattr(a, "shape", ()) for a in args]
    np.broadcast_shapes(*shapes)  # raises ValueError on incompatible shapes
    out_ndim = max((len(s) for s in shapes), default=0)
    expr_inds = tuple(range(out_ndim))[::-1]
    blockwise_args = []
    for a in args:
        nd = getattr(a, "ndim", 0)
        # trailing dims align rightmost (broadcasting); 0-d arrays use ()
        blockwise_args.extend([a, tuple(range(nd))[::-1]])
    return blockwise(
        func, expr_inds, *blockwise_args, dtype=dtype, shape_invariant=True
    )


def map_blocks(
    func: Callable,
    *args,
    dtype=None,
    chunks=None,
    drop_axis=None,
    new_axis=None,
    spec=None,
    **kwargs,
) -> CoreArray:
    """Apply a function to corresponding blocks, possibly changing chunk shape.

    Supports ``block_id`` in *func* via a hidden offsets virtual array
    (reference cubed/core/ops.py:539-565).
    """
    arrays = [a for a in args if isinstance(a, CoreArray)]
    if not arrays:
        # no-input case: build a grid from an empty virtual array
        if chunks is None:
            raise ValueError("chunks must be specified with no array args")
        nc = normalize_chunks(chunks, shape=kwargs.pop("shape"), dtype=dtype)
        return _map_blocks_no_args(func, nc, dtype, spec, **kwargs)

    if drop_axis is None:
        drop_axis = []
    if isinstance(drop_axis, Integral):
        drop_axis = [drop_axis]
    if isinstance(new_axis, Integral):
        new_axis = [new_axis]

    has_block_id = "block_id" in _func_argnames(func)

    x = arrays[0]
    in_ndim = x.ndim
    out_ind_full = list(range(in_ndim))
    out_ind = [i for i in out_ind_full if i not in drop_axis]
    if new_axis:
        # renumber: insert new symbols at the new axis positions
        sym = in_ndim
        for ax in sorted(new_axis):
            out_ind.insert(ax, sym)
            sym += 1

    adjust_chunks = None
    new_axes = {}
    if chunks is not None:
        # explicit output chunks: normalize against derived shape
        nc = chunks
        if isinstance(nc, tuple) and len(nc) > 0 and not isinstance(nc[0], tuple):
            nc = tuple((c,) if isinstance(c, (int, np.integer)) else tuple(c) for c in nc)
            # expand single chunk sizes across the block grid of the mapped dims
        adjust_chunks = {}
        for pos, sym in enumerate(out_ind):
            if isinstance(chunks[pos], (int, np.integer)):
                adjust_chunks[sym] = int(chunks[pos])
            else:
                adjust_chunks[sym] = tuple(chunks[pos])
        # symbols for new axes need sizes
        if new_axis:
            for ax in sorted(new_axis):
                sym = out_ind[ax]
                if isinstance(chunks[ax], (int, np.integer)):
                    new_axes[sym] = int(chunks[ax])
                    adjust_chunks.pop(sym, None)
                else:
                    new_axes[sym] = tuple(chunks[ax])
                    adjust_chunks.pop(sym, None)
    elif new_axis:
        for ax in sorted(new_axis):
            new_axes[out_ind[ax]] = 1

    blockwise_args = []
    for a in args:
        if isinstance(a, CoreArray):
            # 0-d arrays use the EMPTY index (their single block reads via
            # key (name,)), matching elemwise; None would mean dask's
            # "pass the raw argument through", which the runtime's
            # _read_keys has no reader for — a computed 0-d array through
            # astype/map_blocks crashed on exactly that
            blockwise_args.extend([a, tuple(range(a.ndim))])
        else:
            # non-array args are closed over
            raise ValueError("non-array positional args not supported; use kwargs")

    if has_block_id:
        offsets = _offsets_array_for(x)
        numblocks = x.numblocks

        supports_offset = getattr(func, "supports_offset", False)

        def func_with_block_id(*chunk_args, **kw):
            *real, offset = chunk_args
            if supports_offset:
                # trace-friendly: hand the (possibly traced) scalar offset to
                # the kernel; it unravels on device — the op stays jittable
                # and vmappable (no host sync per task)
                return func(*real, offset=offset, numblocks=numblocks, **kw)
            block_id = offset_to_block_id(int(np.asarray(offset).ravel()[0]), numblocks)
            return func(*real, block_id=block_id, **kw)

        func_with_block_id.__name__ = getattr(func, "__name__", "map_blocks")
        if supports_offset:
            # kernel unravels the offset on device: trace/vmap-safe
            func_with_block_id.traced_offsets = True
        if not supports_offset:
            # the offset->block_id conversion syncs to host: the executor must
            # not hand this kernel traced offsets (no vmap, no jit of offsets)
            func_with_block_id.host_block_id = True
        for attr in ("side_inputs", "whole_select", "resident_identity",
                     "whole_concat", "host_data_nbytes"):
            if hasattr(func, attr):
                setattr(func_with_block_id, attr, getattr(func, attr))
        blockwise_args.extend([offsets, tuple(range(in_ndim))])
        return blockwise(
            func_with_block_id,
            tuple(out_ind),
            *blockwise_args,
            dtype=dtype,
            adjust_chunks=adjust_chunks,
            new_axes=new_axes or None,
            align_arrays=False,
            **kwargs,
        )

    return blockwise(
        func,
        tuple(out_ind),
        *blockwise_args,
        dtype=dtype,
        adjust_chunks=adjust_chunks,
        new_axes=new_axes or None,
        **kwargs,
    )


def _offsets_array_for(x: CoreArray):
    """A CoreArray wrapping a VirtualOffsetsArray matching x's block grid."""
    offsets = virtual_offsets(x.numblocks)
    name = gensym("block-ids")
    plan = Plan._new(name, "block_ids", offsets)
    return new_array(name, offsets, x.spec, plan)


def block_index_from_offset(off, axis: int, numblocks: tuple):
    """The ``axis`` block index from a (traced or concrete) linear offset.

    The row-major decode of a VirtualOffsetsArray chunk value; stays a pure
    device expression so offset-seeded kernels jit/vmap (used by the sort
    network's merge routing and arg_reduction's index seeding)."""
    stride = 1
    for nb in numblocks[axis + 1:]:
        stride *= nb
    return (off.ravel()[0] // stride) % numblocks[axis]


def _map_blocks_no_args(func, chunks, dtype, spec, **kwargs):
    spec = spec_from_config(spec)
    shape = tuple(sum(c) for c in chunks)
    temp = empty_virtual_array(shape, dtype=dtype, chunks=chunks, spec=spec)
    return map_blocks(_DropFirst(func), temp, dtype=dtype, **kwargs)


class _DropFirst:
    """Adapter dropping the placeholder chunk arg for no-input map_blocks."""

    def __init__(self, func):
        self.func = func
        self.__name__ = getattr(func, "__name__", "map_blocks")
        import inspect

        try:
            params = inspect.signature(func).parameters
            self._block_id = "block_id" in params
        except (TypeError, ValueError):
            self._block_id = False

    def __call__(self, _placeholder, block_id=None, **kwargs):
        if self._block_id:
            return self.func(block_id=block_id, **kwargs)
        return self.func(**kwargs)


def _func_argnames(func) -> tuple:
    import inspect

    try:
        return tuple(inspect.signature(func).parameters)
    except (TypeError, ValueError):
        return ()


def empty_virtual_array(shape, dtype=np.float64, chunks="auto", spec=None, hidden=True) -> CoreArray:
    spec = spec_from_config(spec)
    outchunks = normalize_chunks(chunks, shape, dtype=dtype)
    target = virtual_empty(shape, dtype=dtype, chunks=to_chunksize(outchunks) if shape else ())
    name = gensym("empty")
    plan = Plan._new(name, "empty", target, None, hidden)
    return new_array(name, target, spec, plan)


def map_direct(
    func: Callable,
    *args: CoreArray,
    shape,
    dtype,
    chunks,
    extra_projected_mem: int,
    spec=None,
    **kwargs,
) -> CoreArray:
    """Map a function over blocks of a new array, with side-input access to
    whole source arrays (any access pattern). Not fusable: side-input reads
    are outside the blockwise memory model. Reference cubed/core/ops.py:646-699.
    """
    from ..array_api.creation_functions import _finalize_spec

    spec = _spec_of(*args, spec=spec)
    nc = normalize_chunks(chunks, shape, dtype=dtype)
    out = empty_virtual_array(shape, dtype=dtype, chunks=nc, spec=spec, hidden=True)

    side_arrays = [a.zarray_maybe_lazy for a in args]

    def new_func(block, block_id=None, **kw):
        # side inputs are opened inside the task
        from ..storage.zarr import open_if_lazy_zarr_array

        opened = [open_if_lazy_zarr_array(s) for s in side_arrays]
        return func(block, *opened, block_id=block_id, **kw)

    new_func.__name__ = getattr(func, "__name__", "map_direct")
    # declare side inputs so residency-based executors materialize them in
    # storage before this op's tasks read them directly; propagate fast-path
    # markers from the inner task body
    new_func.side_inputs = side_arrays
    for attr in ("whole_select", "resident_identity", "whole_concat"):
        if hasattr(func, attr):
            setattr(new_func, attr, getattr(func, attr))

    mapped = map_blocks(
        new_func,
        out,
        dtype=dtype,
        chunks=nc,
        extra_projected_mem=extra_projected_mem,
        fusable=False,
        **kwargs,
    )
    # record the true dependencies in the plan (side inputs), so side-input
    # arrays are created/computed before this op runs
    import networkx as nx

    dag = mapped.plan.dag
    op_node = _producing_op(mapped)
    for a in args:
        dag = nx.compose(a.plan.dag, dag)
        dag.add_edge(a.name, op_node)
    mapped.plan = Plan(dag)
    return mapped


def _producing_op(x: CoreArray) -> str:
    for pred in x.plan.dag.predecessors(x.name):
        return pred
    raise ValueError(f"no producing op for {x.name}")


# ---------------------------------------------------------------------------
# Indexing
# ---------------------------------------------------------------------------


def index(x: CoreArray, key) -> CoreArray:
    """Orthogonal (outer) indexing: ints, slices, one integer-array index.

    Reference cubed/core/ops.py:374-517.
    """
    if not isinstance(key, tuple):
        key = (key,)

    # expand Ellipsis first; None (newaxis) entries consume no input axis
    n_consuming = sum(1 for k in key if k is not None and k is not Ellipsis)
    if n_consuming > x.ndim:
        raise IndexError(f"too many indices for array with {x.ndim} dimensions")
    # note: `Ellipsis in key` would compare numpy-array entries elementwise
    n_ellipsis = sum(1 for k in key if k is Ellipsis)
    if n_ellipsis > 1:
        raise IndexError("an index can only have a single ellipsis ('...')")
    if n_ellipsis:
        i = next(i for i, k in enumerate(key) if k is Ellipsis)
        fill = x.ndim - n_consuming
        key = key[:i] + (slice(None),) * fill + key[i + 1 :]
    key = key + (slice(None),) * (x.ndim - sum(1 for k in key if k is not None))

    # newaxis insert positions in OUTPUT coordinates: slices/arrays keep an
    # axis, ints drop theirs, each None inserts one (applied after squeeze)
    newaxis_positions = []
    _out_pos = 0
    for k in key:
        if k is None:
            newaxis_positions.append(_out_pos)
            _out_pos += 1
        elif not isinstance(k, (int, np.integer)):
            _out_pos += 1
    key = tuple(k for k in key if k is not None)

    # eagerly compute any lazy-array indices (reference ops.py:391-395)
    norm_key = []
    for k in key:
        if isinstance(k, CoreArray):
            norm_key.append(np.asarray(k.compute()))
        elif isinstance(k, (list, np.ndarray)):
            norm_key.append(np.asarray(k))
        else:
            norm_key.append(k)
    key = tuple(norm_key)

    n_array_idx = sum(1 for k in key if isinstance(k, np.ndarray))
    if n_array_idx > 1:
        raise NotImplementedError("Only one integer array index is allowed")

    # per-axis selections; ints drop the axis afterwards
    int_axes = [i for i, k in enumerate(key) if isinstance(k, (int, np.integer))]
    selections = []
    for ax, k in enumerate(key):
        size = x.shape[ax]
        if isinstance(k, (int, np.integer)):
            kk = int(k) + (size if k < 0 else 0)
            if not (0 <= kk < size):
                raise IndexError(f"index {k} out of bounds for axis {ax} (size {size})")
            selections.append(np.array([kk]))
        elif isinstance(k, slice):
            selections.append(k)
        else:
            arr = np.asarray(k)
            if arr.dtype == bool:
                raise NotImplementedError("boolean array indexing is not supported")
            arr = np.where(arr < 0, arr + size, arr)
            selections.append(arr.astype(np.int64))

    steps = [
        (s.step or 1) if isinstance(s, slice) else 1 for s in selections
    ]

    out_shape = []
    for ax, s in enumerate(selections):
        if isinstance(s, slice):
            start, stop, step = s.indices(x.shape[ax])
            out_shape.append(max(0, (stop - start + (step - 1 if step > 0 else step + 1)) // step))
        else:
            out_shape.append(len(s))
    out_shape = tuple(out_shape)

    if out_shape == x.shape and all(
        isinstance(s, slice) and s.indices(x.shape[i]) == (0, x.shape[i], 1)
        for i, s in enumerate(selections)
    ):
        result = x
    else:
        # output keeps the input chunksize (regular chunks)
        out_chunksize = tuple(
            min(cs, osh) if osh > 0 else 1
            for cs, osh in zip(x.chunksize, out_shape)
        )
        out_chunks = normalize_chunks(out_chunksize, out_shape, dtype=x.dtype)

        # resolved global selections (start offsets etc.) for task-side math
        resolved = []
        for ax, s in enumerate(selections):
            if isinstance(s, slice):
                resolved.append(s.indices(x.shape[ax]))
            else:
                resolved.append(s)

        extra_projected_mem = x.chunkmem + chunk_memory(x.dtype, out_chunksize)

        result = map_direct(
            _IndexRead(out_chunks, resolved),
            x,
            shape=out_shape,
            dtype=x.dtype,
            chunks=out_chunks,
            extra_projected_mem=extra_projected_mem,
        )

    if int_axes:
        from ..array_api.manipulation_functions import _squeeze_axes

        result = _squeeze_axes(result, tuple(int_axes))
    for pos in newaxis_positions:
        from ..array_api.manipulation_functions import expand_dims

        result = expand_dims(result, axis=pos)
    return result


class _IndexRead:
    """Task body for index: read this output block's selection via oindex.

    ``whole_select`` exposes the global per-axis selection so residency-based
    executors can realize the whole index as one device-side gather instead of
    per-task storage reads.
    """

    __name__ = "index"

    def __init__(self, out_chunks, selections):
        self.out_chunks = out_chunks
        self.whole_select = selections

    def __call__(self, block, zarray, block_id=None):
        sel = []
        for ax, (bid, chunks_ax, s) in enumerate(
            zip(block_id, self.out_chunks, self.whole_select)
        ):
            start = sum(chunks_ax[:bid])
            stop = start + chunks_ax[bid]
            if isinstance(s, tuple):  # resolved slice (start, stop, step)
                s0, s1, st = s
                hi = s0 + stop * st
                if st < 0 and hi < 0:
                    # a computed stop of -1 means "walked past index 0";
                    # as a literal slice bound it would wrap to the end
                    hi = None
                sel.append(slice(s0 + start * st, hi, st))
            else:
                sel.append(s[start:stop])
        out = zarray.oindex[tuple(sel)]
        return numpy_array_to_backend_array(out)


# ---------------------------------------------------------------------------
# Rechunk / merge_chunks
# ---------------------------------------------------------------------------


def rechunk(x: CoreArray, chunks, target_store=None) -> CoreArray:
    """Change the chunking of x without changing its shape."""
    if isinstance(chunks, dict):
        chunks = {k: v for k, v in chunks.items()}
        chunks = tuple(chunks.get(i, x.chunksize[i]) for i in range(x.ndim))
    if isinstance(chunks, (int, np.integer)):
        chunks = (int(chunks),) * x.ndim
    norm = normalize_chunks(chunks, x.shape, dtype=x.dtype)
    target_chunksize = to_chunksize(norm) if x.shape else ()
    if target_chunksize == x.chunksize:
        return x

    spec = x.spec
    name = gensym("array")
    if target_store is None:
        target_store = new_temp_path(name, spec)
    temp_store = new_temp_path(f"{name}-int", spec)
    ops = primitive_rechunk(
        x.zarray_maybe_lazy,
        source_chunks=x.chunksize,
        target_chunks=target_chunksize,
        allowed_mem=spec.allowed_mem,
        reserved_mem=spec.reserved_mem,
        target_store=target_store,
        temp_store=temp_store,
        storage_options=spec.storage_options,
    )
    # chain the staged copies (1 op for direct, 2 for min-intermediate, N for
    # a multistage geometric plan) into plan nodes
    prev = x
    for i, op in enumerate(ops):
        last = i == len(ops) - 1
        nm = name if last else gensym("array")
        plan = Plan._new(nm, "rechunk", op.target_array, op, not last, prev)
        prev = new_array(nm, op.target_array, spec, plan)
    return prev


def merge_chunks(x: CoreArray, chunks) -> CoreArray:
    """Coalesce chunks: target chunksize must be a multiple of the current."""
    target_chunksize = chunks if isinstance(chunks, tuple) else tuple(chunks)
    if len(target_chunksize) != x.ndim:
        raise ValueError(f"chunks {chunks} must have {x.ndim} dimensions")
    if any(
        t % c != 0 and t != s
        for t, c, s in zip(target_chunksize, x.chunksize, x.shape)
    ):
        raise ValueError(
            f"merge_chunks: target chunks {chunks} must be a multiple of the "
            f"current chunks {x.chunksize}"
        )
    target_chunks = normalize_chunks(target_chunksize, x.shape, dtype=x.dtype)
    extra_projected_mem = chunk_memory(x.dtype, to_chunksize(target_chunks)) + x.chunkmem
    return map_direct(
        _MergedChunkRead(target_chunks),
        x,
        shape=x.shape,
        dtype=x.dtype,
        chunks=target_chunks,
        extra_projected_mem=extra_projected_mem,
    )


class _MergedChunkRead:
    """Task body for merge_chunks. ``resident_identity`` tells residency-based
    executors the values pass through unchanged (chunking is metadata)."""

    __name__ = "merge_chunks"
    resident_identity = True

    def __init__(self, target_chunks):
        self.target_chunks = target_chunks

    def __call__(self, block, zarray, block_id=None):
        sel = get_item(self.target_chunks, block_id)
        return numpy_array_to_backend_array(zarray[sel])


# ---------------------------------------------------------------------------
# Reductions (tree formulation)
# ---------------------------------------------------------------------------


def reduction(
    x: CoreArray,
    func: Callable,
    combine_func: Optional[Callable] = None,
    aggregate_func: Optional[Callable] = None,
    axis=None,
    intermediate_dtype=None,
    dtype=None,
    keepdims: bool = False,
    split_every: Optional[int] = None,
    extra_func_kwargs: Optional[dict] = None,
) -> CoreArray:
    """Tree reduction: per-block partial reduce, then rounds of bounded
    combines until one block remains per reduced axis, then optional aggregate.

    On the TPU executor the combine rounds over mesh-sharded axes lower to
    ``lax.psum``-style collective trees (reference: round-based merge/combine
    through storage, cubed/core/ops.py:790-1090).
    """
    if combine_func is None:
        combine_func = func
    if axis is None:
        axis = tuple(range(x.ndim))
    if isinstance(axis, (int, np.integer)):
        axis = (int(axis),)
    axis = tuple(ax % x.ndim for ax in axis)
    if intermediate_dtype is None:
        intermediate_dtype = dtype

    kw = dict(extra_func_kwargs or {})

    split = split_every or 4
    fields = _fields_of(intermediate_dtype)
    if fields is not None:
        if aggregate_func is None:
            raise ValueError(
                "structured intermediate_dtype requires aggregate_func"
            )
        # pytree intermediates ride as one PLAIN array per field produced by
        # multi-output ops — no structured-dtype storage anywhere in the
        # tree, so intermediates shard under a mesh like any other array
        # (structured arrays can't ride make_array_from_callback). The
        # reference instead stores a single structured array
        # (cubed/array_api/statistical_functions.py:33-36).
        parts = reduction_fields(
            x, func, combine_func, axis=axis, fields=fields,
            split_every=split, extra_func_kwargs=kw,
        )
        result = _aggregate_fields(parts, aggregate_func, dtype, list(fields))
    else:
        # initial per-block reduction (reduced axes -> size 1)
        adjust = {i: 1 for i in range(x.ndim) if i in axis}
        inds = tuple(range(x.ndim))
        result = blockwise(
            partial(_initial_reduce, func=func, axis=axis, kw=kw),
            inds,
            x,
            inds,
            dtype=intermediate_dtype,
            adjust_chunks=adjust,
        )

        # combine rounds
        while any(result.numblocks[ax] > 1 for ax in axis):
            result = partial_reduce(
                result,
                _StreamingCombine(combine_func, axis, kw),
                split_every={ax: split for ax in axis},
                dtype=intermediate_dtype,
            )

        # aggregate
        if aggregate_func is not None:
            result = map_blocks(
                partial(_apply_aggregate, aggregate_func=aggregate_func),
                result, dtype=dtype,
            )

    if not keepdims:
        from ..array_api.manipulation_functions import _squeeze_axes

        result = _squeeze_axes(result, axis)

    if dtype is not None and result.dtype != np.dtype(dtype):
        from ..array_api.data_type_functions import astype

        result = astype(result, dtype)
    return result


def _initial_reduce(chunk, *, func, axis, kw):
    return func(chunk, axis=axis, keepdims=True, **kw)


class _StreamingCombine:
    """Combine a group of blocks along reduced axes.

    Called with an *iterator* of chunks it accumulates pairwise (bounded
    memory: one concat buffer regardless of group size — the oracle executors'
    path). ``combine_region`` combines a single merged contiguous region in
    one shot — the TPU executor uses it to turn a whole group into one jitted
    reduction with no streaming dispatches. Both paths require the combine to
    be associative+commutative over the reduced axes, which reduction
    combiners are by contract.
    """

    __name__ = "partial_reduce"

    def __init__(self, combine_func, axis: tuple, kw: dict):
        self.combine_func = combine_func
        self.axis = axis
        self.kw = kw
        # propagate the combine's semantic tag (e.g. "sum") — the seam a
        # substituted region kernel keys on (see the note in
        # array_api/statistical_functions.py)
        self.reduce_kind = getattr(combine_func, "reduce_kind", None) or (
            "sum" if combine_func is nxp.sum else None
        )

    def __call__(self, chunks_iter):
        acc = None
        axis = self.axis
        for chunk in chunks_iter:
            if acc is None:
                acc = chunk
            else:
                merged = _concat_pytree(acc, chunk, axis[0] if len(axis) == 1 else axis)
                acc = self.combine_func(merged, axis=axis, keepdims=True, **self.kw)
        return acc

    def combine_region(self, region):
        return self.combine_func(region, axis=self.axis, keepdims=True, **self.kw)


def _concat_pytree(a, b, axis):
    ax = axis if isinstance(axis, int) else axis[0]
    if isinstance(a, dict):
        return {k: _concat_pytree(a[k], b[k], ax) for k in a}
    return nxp.concatenate([a, b], axis=ax)


def _apply_aggregate(chunk, *, aggregate_func):
    return aggregate_func(chunk)


def partial_reduce(
    x: CoreArray,
    func: Callable,
    split_every: dict,
    dtype=None,
) -> CoreArray:
    """Combine groups of blocks along reduced axes (one tree level).

    The block function yields an *iterator* of input keys so the task streams
    chunks one at a time (bounded memory regardless of group size).
    Reference cubed/core/ops.py:1033-1090.
    """
    # each merged group of k blocks combines (keepdims) into one size-1 block
    chunks = tuple(
        (1,) * math.ceil(len(c) / split_every[i]) if i in split_every else c
        for i, c in enumerate(x.chunks)
    )
    shape = tuple(sum(c) for c in chunks)

    in_numblocks = x.numblocks
    x_name = x.name

    def block_function(out_key):
        out_coords = out_key[1:]
        ranges = []
        for i, bi in enumerate(out_coords):
            if i in split_every:
                k = split_every[i]
                start = bi * k
                stop = min(start + k, in_numblocks[i])
                ranges.append(range(start, stop))
            else:
                ranges.append(range(bi, bi + 1))
        return (iter((x_name, *idx) for idx in itertools.product(*ranges)),)

    extra_projected_mem = 2 * x.chunkmem  # accumulator + concat buffer
    return general_blockwise(
        func,
        block_function,
        x,
        shape=shape,
        dtype=dtype if dtype is not None else x.dtype,
        chunks=chunks,
        extra_projected_mem=extra_projected_mem,
        num_input_blocks=(max(split_every.values()),),
        fusable=False,
        op_name="partial_reduce",
    )


def reduction_fields(
    x: CoreArray,
    func: Callable,
    combine_func: Callable,
    *,
    axis: tuple,
    fields: dict,
    split_every: int = 4,
    extra_func_kwargs: Optional[dict] = None,
):
    """The pytree-field reduction TREE without the final aggregate: per-
    block ``func`` produces a dict of field arrays, combine rounds shrink
    the reduced axes to one block, and the returned dict of (tiny,
    1-block-per-reduced-axis) field arrays is ready for one or SEVERAL
    cheap aggregates — e.g. histogram's single-pass {lo, hi} extent scan
    reads the data once and aggregates both fields from the final block."""
    kw = dict(extra_func_kwargs or {})
    parts = _multi_field_map(
        x,
        partial(_initial_reduce, func=func, axis=axis, kw=kw),
        fields,
        chunks=tuple(
            (1,) * x.numblocks[i] if i in axis else c
            for i, c in enumerate(x.chunks)
        ),
        op_name="initial_reduce",
    )
    while any(parts[0].numblocks[ax] > 1 for ax in axis):
        parts = partial_reduce_multi(
            parts,
            _StreamingCombineMulti(combine_func, axis, kw, list(fields)),
            split_every={ax: split_every for ax in axis},
            fields=fields,
        )
    return parts


def _fields_of(intermediate_dtype) -> Optional[dict]:
    """{field name -> plain dtype} for a structured dtype, else None."""
    if intermediate_dtype is None:
        return None
    dt = np.dtype(intermediate_dtype)
    if dt.fields is None:
        return None
    return {name: dt.fields[name][0] for name in dt.names}


def _multi_field_map(
    x: CoreArray,
    kernel: Callable,
    fields: dict,
    chunks,
    op_name: str,
) -> tuple:
    """One multi-output op mapping ``kernel`` (returning {field: chunk})
    1:1 over x's blocks; each field becomes a PLAIN array output."""
    names = list(fields)
    x_name = x.name
    shape = tuple(sum(c) for c in chunks)

    def block_function(out_key):
        return ((x_name, *out_key[1:]),)

    def field_kernel(chunk):
        d = kernel(chunk)
        return tuple(d[k] for k in names)

    field_kernel.__name__ = getattr(kernel, "__name__", op_name)

    return general_blockwise(
        field_kernel,
        block_function,
        x,
        shape=[shape] * len(names),
        dtype=[fields[k] for k in names],
        chunks=chunks,
        op_name=op_name,
    )


def partial_reduce_multi(
    parts: Sequence[CoreArray],
    combiner: Callable,
    split_every: dict,
    fields: dict,
) -> tuple:
    """One tree level over pytree intermediates held as N field arrays:
    one multi-output op streams N zipped block groups -> N outputs.

    The multi-field analogue of :func:`partial_reduce` (same grouping, same
    bounded-memory streaming contract)."""
    x0 = parts[0]
    chunks = tuple(
        (1,) * math.ceil(len(c) / split_every[i]) if i in split_every else c
        for i, c in enumerate(x0.chunks)
    )
    shape = tuple(sum(c) for c in chunks)
    in_numblocks = x0.numblocks
    part_names = [p.name for p in parts]

    def block_function(out_key):
        out_coords = out_key[1:]
        ranges = []
        for i, bi in enumerate(out_coords):
            if i in split_every:
                k = split_every[i]
                start = bi * k
                stop = min(start + k, in_numblocks[i])
                ranges.append(range(start, stop))
            else:
                ranges.append(range(bi, bi + 1))
        idxs = list(itertools.product(*ranges))
        return tuple(
            iter([(pn, *idx) for idx in idxs]) for pn in part_names
        )

    # accumulator + concat buffer per field, streamed one group block at a
    # time (same model as partial_reduce)
    extra_projected_mem = 2 * sum(p.chunkmem for p in parts)
    return general_blockwise(
        combiner,
        block_function,
        *parts,
        shape=[shape] * len(parts),
        dtype=[fields[k] for k in fields],
        chunks=chunks,
        extra_projected_mem=extra_projected_mem,
        num_input_blocks=(max(split_every.values()),) * len(parts),
        fusable=False,
        op_name="partial_reduce",
    )


class _StreamingCombineMulti:
    """Multi-field analogue of :class:`_StreamingCombine`: streams N zipped
    block iterators, reassembling the {field: chunk} pytree per step for the
    dict-based combine, and returns a tuple in field order.

    ``combine_region`` lets the TPU executor combine whole contiguous
    regions (one per field) in a single jitted call."""

    __name__ = "partial_reduce"

    def __init__(self, combine_func, axis: tuple, kw: dict, names: list):
        self.combine_func = combine_func
        self.axis = axis
        self.kw = kw
        self.names = names

    def __call__(self, *iters):
        acc = None
        axis = self.axis
        for vals in zip(*iters):
            d = dict(zip(self.names, vals))
            if acc is None:
                acc = d
            else:
                merged = _concat_pytree(
                    acc, d, axis[0] if len(axis) == 1 else axis
                )
                acc = self.combine_func(
                    merged, axis=axis, keepdims=True, **self.kw
                )
        return tuple(acc[k] for k in self.names)

    def combine_region(self, *regions):
        d = dict(zip(self.names, regions))
        out = self.combine_func(d, axis=self.axis, keepdims=True, **self.kw)
        return tuple(out[k] for k in self.names)


def _aggregate_fields(
    parts: Sequence[CoreArray], aggregate_func: Callable, dtype, names: list
) -> CoreArray:
    """Final aggregate over N field arrays -> one plain array (1:1 blocks)."""
    inds = tuple(range(parts[0].ndim))

    def agg_kernel(*chunks):
        return aggregate_func(dict(zip(names, chunks)))

    agg_kernel.__name__ = getattr(aggregate_func, "__name__", "aggregate")
    args = []
    for p in parts:
        args.extend([p, inds])
    return blockwise(agg_kernel, inds, *args, dtype=dtype)


def _merged_chunklist(chunks_1d: tuple[int, ...], k: int) -> tuple[int, ...]:
    out = []
    for i in range(0, len(chunks_1d), k):
        out.append(sum(chunks_1d[i : i + k]))
    return tuple(out)


def arg_reduction(
    x: CoreArray, func: Callable, cmp_func: Callable, axis=None, dtype=np.int64
) -> CoreArray:
    """argmin/argmax via an {i, v} tree reduction with absolute indices.

    The intermediates ride as TWO plain arrays (int64 indices + values)
    produced by multi-output ops, and the per-block seeding reads the block
    index from the traced linear offset — the whole tree jits/vmaps (the
    reference seeds from a host block_id over a structured array,
    cubed/core/ops.py:1093-1153)."""
    if axis is None:
        raise ValueError("arg_reduction requires an axis (flatten first)")
    axis = int(axis) % x.ndim

    starts = np.cumsum([0] + list(x.chunks[axis][:-1]), dtype=np.int64)
    numblocks = x.numblocks
    offsets = _offsets_array_for(x)
    x_name, o_name = x.name, offsets.name
    out_chunks = tuple(
        (1,) * numblocks[i] if i == axis else x.chunks[i]
        for i in range(x.ndim)
    )
    shape = tuple(sum(c) for c in out_chunks)

    def block_function(out_key):
        coords = out_key[1:]
        return ((x_name, *coords), (o_name, *coords))

    def arg_initial(chunk, offset):
        # axis block index from the (possibly traced) linear offset;
        # `starts` is a tiny per-grid constant, gathered on device
        bi = block_index_from_offset(offset, axis, numblocks)
        start = nxp.take(nxp.asarray(starts), bi)
        i = func(chunk, axis=axis, keepdims=True)  # local argmin/argmax
        v = cmp_func(chunk, axis=axis, keepdims=True)
        return nxp.asarray(i, dtype=np.int64) + start, v

    arg_initial.traced_offsets = True
    arg_initial.__name__ = "arg_initial"

    fields = {"i": np.dtype(np.int64), "v": np.dtype(x.dtype)}
    parts = general_blockwise(
        arg_initial,
        block_function,
        x,
        offsets,
        shape=[shape, shape],
        dtype=[fields["i"], fields["v"]],
        chunks=out_chunks,
        op_name="arg_initial",
    )
    def arg_combine(d, axis=None, keepdims=True):
        ax = axis[0] if isinstance(axis, tuple) else axis
        local = func(d["v"], axis=ax, keepdims=True)
        return {
            "i": nxp.take_along_axis(d["i"], local, axis=ax),
            "v": cmp_func(d["v"], axis=ax, keepdims=True),
        }

    arg_combine.__name__ = "arg_combine"

    split = 4
    while parts[0].numblocks[axis] > 1:
        parts = partial_reduce_multi(
            parts,
            _StreamingCombineMulti(arg_combine, (axis,), {}, list(fields)),
            split_every={axis: split},
            fields=fields,
        )
    result = parts[0]
    if result.dtype != np.dtype(dtype):
        result = map_blocks(
            lambda c: nxp.asarray(c, dtype=dtype), result, dtype=dtype
        )
    from ..array_api.manipulation_functions import _squeeze_axes

    return _squeeze_axes(result, (axis,))




# ---------------------------------------------------------------------------
# squeeze / unify
# ---------------------------------------------------------------------------


def squeeze(x: CoreArray, axis=None) -> CoreArray:
    from ..array_api.manipulation_functions import squeeze as _squeeze

    return _squeeze(x, axis=axis)


def unify_chunks(*args):
    """Align chunking of arrays sharing index symbols; rechunk as needed.

    Args are (array, ind) pairs. Returns (chunkss, arrays).
    Reference cubed/core/ops.py:1172-1219 (there via dask's common_blockdim,
    which raises when the common refinement is not zarr-regular). Here any
    misaligned-but-equal-extent chunkings unify: every array's chunks are
    already zarr-regular, so the smallest per-symbol chunksize is a regular
    target every input can rechunk to — rechunk regrids across arbitrary
    boundaries (storage round-trip, or an in-HBM reshard on the TPU
    executor), so boundary-union refinements are unnecessary, and the
    smallest-chunksize choice keeps per-task memory bounded.
    """
    arrays = list(args[0::2])
    inds = list(args[1::2])

    chunkss: dict = {}
    for a, ind in zip(arrays, inds):
        if ind is None:
            continue
        for sym, c, extent in zip(ind, a.chunks, a.shape):
            if sum(c) == 1 and len(c) == 1:
                chunkss.setdefault(sym, c)  # broadcast candidate
            elif sym not in chunkss or sum(chunkss[sym]) == 1:
                chunkss[sym] = c
            else:
                prev = chunkss[sym]
                if sum(prev) != sum(c):
                    raise ValueError(
                        f"Chunks do not align for symbol {sym!r}: "
                        f"{prev} vs {c} (extents {sum(prev)} != {sum(c)})"
                    )
                if c != prev:
                    smallest = min(prev[0], c[0])
                    chunkss[sym] = normalize_chunks(
                        (smallest,), (extent,), dtype=a.dtype
                    )[0]

    unified = []
    for a, ind in zip(arrays, inds):
        if ind is None:
            unified.append(a)
            continue
        target = tuple(
            chunkss[sym] if sum(chunkss[sym]) == a.shape[dim] else a.chunks[dim]
            for dim, sym in enumerate(ind)
        )
        if target != a.chunks:
            unified.append(rechunk(a, target))
        else:
            unified.append(a)
    return chunkss, unified


def map_overlap(
    func: Callable,
    x: CoreArray,
    *,
    depth,
    boundary="reflect",
    dtype=None,
    trim: bool = True,
) -> CoreArray:
    """Map a function over blocks extended by ``depth`` halo elements on
    each side — the chunked stencil primitive (dask.array.map_overlap
    semantics; the reference has no overlap machinery at all).

    Each task reads its block PLUS the halo straight from the source
    (one extended region read — no separate halo-exchange ops), pads at
    the array boundary per ``boundary`` ("reflect", "nearest",
    "periodic", or a constant number), applies ``func`` to the extended
    block, and (with ``trim=True``, the default) trims ``depth`` back
    off the result. Per-task memory is block + halo — priced into the
    plan; the array may exceed ``allowed_mem``.

    ``depth``: int (all axes) or per-axis sequence/dict of ints.
    """
    if dtype is None:
        dtype = x.dtype
    if isinstance(depth, (int, np.integer)):
        depths = [int(depth)] * x.ndim
    elif isinstance(depth, dict):
        norm = {}
        for ax, d in depth.items():
            if not -x.ndim <= ax < x.ndim:
                raise IndexError(
                    f"map_overlap: depth axis {ax} is out of bounds for "
                    f"array of dimension {x.ndim}"
                )
            norm[ax % x.ndim] = int(d)
        depths = [norm.get(ax, 0) for ax in range(x.ndim)]
    else:
        depths = [int(d) for d in depth]
        if len(depths) != x.ndim:
            raise ValueError(
                f"depth has {len(depths)} entries for {x.ndim} axes"
            )
    if any(d < 0 for d in depths):
        raise ValueError("map_overlap: depth must be non-negative")
    if any(d > s for d, s in zip(depths, x.shape)):
        raise ValueError("map_overlap: depth exceeds the array extent")
    constant = None
    if not isinstance(boundary, str):
        constant = float(boundary)
    elif boundary not in ("reflect", "nearest", "periodic"):
        raise ValueError(f"map_overlap: unsupported boundary {boundary!r}")

    chunks = x.chunks
    shape = x.shape
    ndim = x.ndim

    periodic = boundary == "periodic" and constant is None

    def _read_overlap(block, zarray, block_id=None):
        if periodic:
            # wrapped halos come from the FAR end of the global array; the
            # window's index range per axis splits into <= 3 contiguous
            # runs mod n — read the cartesian product of runs and stitch
            # (touches only halo-sized extra data; no extended copy of x)
            runs = []
            for ax in range(ndim):
                start = sum(chunks[ax][: block_id[ax]])
                stop = start + chunks[ax][block_id[ax]]
                d = depths[ax]
                n_ax = shape[ax]
                lo, hi = start - d, stop + d
                ax_runs = []
                if lo < 0:
                    ax_runs.append(slice(n_ax + lo, n_ax))
                ax_runs.append(slice(max(0, lo), min(n_ax, hi)))
                if hi > n_ax:
                    ax_runs.append(slice(0, hi - n_ax))
                runs.append(ax_runs)

            def rec(ax, prefix):
                if ax == ndim:
                    return np.asarray(zarray[tuple(prefix)])
                parts = [rec(ax + 1, prefix + [s]) for s in runs[ax]]
                return (
                    np.concatenate(parts, axis=ax)
                    if len(parts) > 1 else parts[0]
                )

            data = rec(0, [])
            out = func(numpy_array_to_backend_array(data))
        else:
            sel = []
            pads = []
            for ax in range(ndim):
                start = sum(chunks[ax][: block_id[ax]])
                stop = start + chunks[ax][block_id[ax]]
                d = depths[ax]
                lo = start - d
                hi = stop + d
                pad_lo = max(0, -lo)
                pad_hi = max(0, hi - shape[ax])
                sel.append(slice(max(0, lo), min(shape[ax], hi)))
                pads.append((pad_lo, pad_hi))
            data = np.asarray(zarray[tuple(sel)])
            if any(p != (0, 0) for p in pads):
                if constant is not None:
                    data = np.pad(data, pads, mode="constant",
                                  constant_values=constant)
                elif boundary == "nearest":
                    data = np.pad(data, pads, mode="edge")
                else:
                    # dask map_overlap "reflect" INCLUDES the edge element
                    # (numpy calls this "symmetric")
                    data = np.pad(data, pads, mode="symmetric")
            out = func(numpy_array_to_backend_array(data))
        if trim:
            trim_sel = tuple(
                slice(depths[ax], out.shape[ax] - depths[ax] or None)
                for ax in range(ndim)
            )
            out = out[trim_sel]
        return out

    _read_overlap.__name__ = getattr(func, "__name__", "map_overlap")

    halo_elems = 1
    for ax in range(ndim):
        halo_elems *= x.chunksize[ax] + 2 * depths[ax]
    # the read buffer + pad copy carry the INPUT dtype; func's result the
    # output dtype — price with the wider of the two
    extra = 4 * halo_elems * max(
        np.dtype(x.dtype).itemsize, np.dtype(dtype).itemsize
    )

    if trim:
        out_shape, out_chunks = shape, chunks
    else:
        # dask semantics: the untrimmed result keeps its halo, so every
        # output block is the EXTENDED block — chunks grow by 2*depth per
        # axis (numblocks unchanged, so block ids still address the same
        # source block)
        out_chunks = tuple(
            tuple(c + 2 * depths[ax] for c in chunks[ax])
            for ax in range(ndim)
        )
        out_shape = tuple(sum(c) for c in out_chunks)

    return map_direct(
        _read_overlap,
        x,
        shape=out_shape,
        dtype=np.dtype(dtype),
        chunks=out_chunks,
        extra_projected_mem=extra,
        spec=x.spec,
    )
