"""Array-API statistical functions (reductions).

``mean``/``var``/``std`` use dict-of-arrays (pytree) intermediates instead of
the reference's Zarr structured dtypes — jax has no structured arrays, and
pytrees jit cleanly. The tree machinery stores each field as a PLAIN array
written by multi-output ops (core/ops.py reduction + partial_reduce_multi),
so intermediates shard under a device mesh like any other array; the
structured np.dtype passed as ``intermediate_dtype`` only declares the field
names/dtypes.
Reference parity: cubed/array_api/statistical_functions.py (156 LoC).
"""

from __future__ import annotations

import numpy as np

from ..backend_array_api import nxp
from ..core.ops import reduction
from .dtypes import (
    _numeric_dtypes,
    _real_floating_dtypes,
    _real_numeric_dtypes,
    _signed_integer_dtypes,
    _unsigned_integer_dtypes,
    complex64,
    complex128,
    float32,
    float64,
    int64,
    uint64,
)


def max(x, /, *, axis=None, keepdims=False, split_every=None):  # noqa: A001
    if x.dtype not in _real_numeric_dtypes:
        raise TypeError("Only real numeric dtypes are allowed in max")
    return reduction(
        x, nxp.max, axis=axis, dtype=x.dtype, keepdims=keepdims, split_every=split_every
    )


def min(x, /, *, axis=None, keepdims=False, split_every=None):  # noqa: A001
    if x.dtype not in _real_numeric_dtypes:
        raise TypeError("Only real numeric dtypes are allowed in min")
    return reduction(
        x, nxp.min, axis=axis, dtype=x.dtype, keepdims=keepdims, split_every=split_every
    )


def sum(x, /, *, axis=None, dtype=None, keepdims=False, split_every=None):  # noqa: A001
    if x.dtype not in _numeric_dtypes:
        raise TypeError("Only numeric dtypes are allowed in sum")
    if dtype is None:
        if x.dtype in _signed_integer_dtypes:
            dtype = int64
        elif x.dtype in _unsigned_integer_dtypes:
            dtype = uint64
        elif x.dtype == float32:
            dtype = float32
        elif x.dtype == complex64:
            dtype = complex64
        else:
            dtype = x.dtype
    dtype = np.dtype(dtype)
    return reduction(
        x,
        _sum_with_dtype,
        combine_func=_sum_with_dtype,
        axis=axis,
        intermediate_dtype=dtype,
        dtype=dtype,
        keepdims=keepdims,
        split_every=split_every,
        extra_func_kwargs=dict(dtype=dtype),
    )


def _sum_with_dtype(a, axis=None, keepdims=False, dtype=None):
    return nxp.sum(a, axis=axis, keepdims=keepdims, dtype=dtype)


# semantic tag on the combine (e.g. "sum"): the seam for kernel substitution
# (hand-written streaming-reduction kernels consumed it before they were
# retired; git history keeps them)
_sum_with_dtype.reduce_kind = "sum"


def prod(x, /, *, axis=None, dtype=None, keepdims=False, split_every=None):
    if x.dtype not in _numeric_dtypes:
        raise TypeError("Only numeric dtypes are allowed in prod")
    if dtype is None:
        if x.dtype in _signed_integer_dtypes:
            dtype = int64
        elif x.dtype in _unsigned_integer_dtypes:
            dtype = uint64
        elif x.dtype == float32:
            dtype = float32
        elif x.dtype == complex64:
            dtype = complex64
        else:
            dtype = x.dtype
    dtype = np.dtype(dtype)
    return reduction(
        x,
        _prod_with_dtype,
        combine_func=_prod_with_dtype,
        axis=axis,
        intermediate_dtype=dtype,
        dtype=dtype,
        keepdims=keepdims,
        split_every=split_every,
        extra_func_kwargs=dict(dtype=dtype),
    )


def _prod_with_dtype(a, axis=None, keepdims=False, dtype=None):
    return nxp.prod(a, axis=axis, keepdims=keepdims, dtype=dtype)


# -- mean / var / std (pytree intermediates) --------------------------------

#: field declaration for the {n, total} intermediate (each field rides as a
#: plain array through the multi-output tree; the reference instead stores a
#: single structured array, cubed/array_api/statistical_functions.py:33-36)
def _mean_intermediate_dtype(x_dtype):
    return np.dtype([("n", np.int64), ("total", np.float64)])


def mean(x, /, *, axis=None, keepdims=False, split_every=None):
    if x.dtype not in _real_floating_dtypes:
        raise TypeError("Only real floating-point dtypes are allowed in mean")
    dtype = x.dtype
    intermediate_dtype = _mean_intermediate_dtype(dtype)
    return reduction(
        x,
        _mean_func,
        combine_func=_mean_combine,
        aggregate_func=_mean_aggregate,
        axis=axis,
        intermediate_dtype=intermediate_dtype,
        dtype=dtype,
        keepdims=keepdims,
        split_every=split_every,
    )


def _numel(x, axis=None, keepdims=False, dtype=np.float64):
    """Number of elements along axis, broadcast to the reduced shape."""
    shape = x.shape
    n = 1
    for ax in axis:
        n *= shape[ax]
    reduced_shape = tuple(
        1 if ax in axis else s for ax, s in enumerate(shape)
    )
    return nxp.broadcast_to(nxp.asarray(n, dtype=dtype), reduced_shape)


def _mean_func(a, axis=None, keepdims=True, **kwargs):
    n = _numel(a, axis=axis, keepdims=keepdims, dtype=np.int64)
    total = nxp.sum(a, axis=axis, keepdims=keepdims, dtype=np.float64)
    return {"n": n, "total": total}


def _mean_combine(a, axis=None, keepdims=True, **kwargs):
    n = nxp.sum(a["n"], axis=axis, keepdims=keepdims)
    total = nxp.sum(a["total"], axis=axis, keepdims=keepdims)
    return {"n": n, "total": total}


def _mean_aggregate(a):
    return nxp.divide(a["total"], a["n"])


def _var_intermediate_dtype(x_dtype):
    return np.dtype([("n", np.int64), ("mu", np.float64), ("M2", np.float64)])


def var(x, /, *, axis=None, correction=0.0, keepdims=False, split_every=None):
    """Variance via parallel Welford (Chan et al.) combination."""
    if x.dtype not in _real_floating_dtypes:
        raise TypeError("Only real floating-point dtypes are allowed in var")
    dtype = x.dtype
    intermediate_dtype = _var_intermediate_dtype(dtype)
    import functools

    return reduction(
        x,
        _var_func,
        combine_func=_var_combine,
        aggregate_func=functools.partial(_var_aggregate, correction=correction),
        axis=axis,
        intermediate_dtype=intermediate_dtype,
        dtype=dtype,
        keepdims=keepdims,
        split_every=split_every,
    )


def _var_func(a, axis=None, keepdims=True, **kwargs):
    n = _numel(a, axis=axis, dtype=np.int64)
    mu = nxp.mean(a, axis=axis, keepdims=keepdims, dtype=np.float64)
    M2 = nxp.sum(
        nxp.square(nxp.subtract(a, mu)), axis=axis, keepdims=keepdims, dtype=np.float64
    )
    return {"n": n, "mu": mu, "M2": M2}


def _var_combine(a, axis=None, keepdims=True, **kwargs):
    # n-ary Chan/Welford merge over ALL reduced axes at once. Reducing only
    # axis[0] broke the executor's region combine, which hands a multi-axis
    # block region in one call (the streaming path masked it by always
    # concatenating along one axis) — caught by the differential fuzzer.
    n = a["n"]
    mu = a["mu"]
    M2 = a["M2"]
    total_n = nxp.sum(n, axis=axis, keepdims=True)
    total = nxp.sum(nxp.multiply(mu, n), axis=axis, keepdims=True)
    new_mu = nxp.divide(total, total_n)
    # M2_total = sum(M2_i) + sum(n_i * (mu_i - new_mu)^2)
    new_M2 = nxp.sum(M2, axis=axis, keepdims=True) + nxp.sum(
        nxp.multiply(n, nxp.square(nxp.subtract(mu, new_mu))), axis=axis, keepdims=True
    )
    return {"n": total_n, "mu": new_mu, "M2": new_M2}


def _var_aggregate(a, correction=0.0):
    d = nxp.subtract(nxp.asarray(a["n"], dtype=np.float64), correction)
    return nxp.divide(a["M2"], d)


def std(x, /, *, axis=None, correction=0.0, keepdims=False, split_every=None):
    from .elementwise_functions import sqrt

    return sqrt(var(x, axis=axis, correction=correction, keepdims=keepdims,
                    split_every=split_every))


# -- cumulative_sum / cumulative_prod (2023.12 standard; beyond-reference) --
#
# The reference has no cumulative scan at all. Chunked prefix scan in two
# passes, both XLA-friendly (cumsum lowers to an associative scan):
#   1. per-block inclusive scan (embarrassingly parallel);
#   2. per-block totals -> one tiny single-chunk exclusive scan along the
#      axis -> per-block offsets, combined into the local scans blockwise.
# All intermediates are bounded: the totals array has one element per block
# along the scanned axis.


def _cumsum_backend(a, axis, dtype):
    return nxp.cumsum(a, axis=axis, dtype=dtype)


def _cumprod_backend(a, axis, dtype):
    return nxp.cumprod(a, axis=axis, dtype=dtype)


def _scan_default_dtype(x_dtype):
    if x_dtype in _signed_integer_dtypes:
        return int64
    if x_dtype in _unsigned_integer_dtypes:
        return uint64
    return x_dtype


def _cumulative(x, axis, dtype, include_initial, *, scan, reduce_fn, identity):
    from ..core.ops import general_blockwise, rechunk

    if x.dtype not in _numeric_dtypes:
        raise TypeError("Only numeric dtypes are allowed in cumulative scans")
    if axis is None:
        if x.ndim > 1:
            raise ValueError(
                "axis must be specified for multi-dimensional cumulative scans"
            )
        axis = 0
    if not -x.ndim <= axis < x.ndim:
        raise IndexError(f"axis {axis} out of bounds for ndim {x.ndim}")
    axis = axis % x.ndim
    if dtype is None:
        dtype = _scan_default_dtype(x.dtype)
    dtype = np.dtype(dtype)

    # CoreArray grids are always the regular blockdims of chunksize, so the
    # offsets pipeline below can rebuild every stage's grid from x.chunksize
    # with block coordinates staying 1:1 with x's
    chunkset = x.chunks
    nb = len(chunkset[axis])

    # 1. per-block inclusive scan
    def _local(a):
        return scan(a, axis, dtype)

    local = general_blockwise(
        _local,
        _same_block(x.name),
        x,
        shape=x.shape,
        dtype=dtype,
        chunks=chunkset,
        op_name="cumulative-local",
    )

    if nb > 1:
        # 2a. per-block totals: grid unchanged except size-1 blocks on axis
        def _totals(a):
            return reduce_fn(a, axis=(axis,), keepdims=True, dtype=dtype)

        totals_chunks = tuple(
            (1,) * nb if d == axis else chunkset[d] for d in range(x.ndim)
        )
        totals_shape = tuple(
            nb if d == axis else s for d, s in enumerate(x.shape)
        )
        totals = general_blockwise(
            _totals,
            _same_block(x.name),
            x,
            shape=totals_shape,
            dtype=dtype,
            chunks=totals_chunks,
            op_name="cumulative-totals",
        )
        # 2b. exclusive scan of the totals along the (now tiny) axis
        one_chunk = tuple(
            nb if d == axis else x.chunksize[d] for d in range(x.ndim)
        )
        gathered = rechunk(totals, one_chunk)

        def _exclusive(t):
            # shift the inclusive scan right by one block-slot, filling with
            # the identity (no subtract/divide: exact for unsigned wrap and
            # for products containing zeros)
            incl = scan(t, axis, dtype)
            head = tuple(
                slice(0, 1) if d == axis else slice(None) for d in range(t.ndim)
            )
            body = tuple(
                slice(0, -1) if d == axis else slice(None) for d in range(t.ndim)
            )
            lead = nxp.full_like(incl[head], identity)
            return nxp.concatenate([lead, incl[body]], axis=axis)

        excl = general_blockwise(
            _exclusive,
            _same_block(gathered.name),
            gathered,
            shape=totals_shape,
            dtype=dtype,
            chunks=gathered.chunks,
            op_name="cumulative-exclusive",
        )
        offsets = rechunk(excl, tuple(
            1 if d == axis else x.chunksize[d] for d in range(x.ndim)
        ))

        # 3. combine: out block i = local block i (+ or *) offsets block i
        l_name, o_name = local.name, offsets.name

        def _block_function(out_key):
            coords = out_key[1:]
            return ((l_name, *coords), (o_name, *coords))

        combine = _combine_add if identity == 0 else _combine_mul
        local = general_blockwise(
            combine,
            _block_function,
            local,
            offsets,
            shape=x.shape,
            dtype=dtype,
            chunks=chunkset,
            op_name="cumulative-combine",
        )

    if include_initial:
        from .creation_functions import full
        from .manipulation_functions import concat

        lead_shape = tuple(
            1 if d == axis else s for d, s in enumerate(x.shape)
        )
        lead = full(lead_shape, identity, dtype=dtype, spec=x.spec)
        return concat([lead, local], axis=axis)
    return local


def _same_block(name):
    def block_function(out_key):
        return ((name, *out_key[1:]),)

    return block_function


def _combine_add(a, o):
    return nxp.add(a, o)


def _combine_mul(a, o):
    return nxp.multiply(a, o)


def cumulative_sum(x, /, *, axis=None, dtype=None, include_initial=False):
    """Cumulative sum along ``axis`` (array-api 2023.12; reference gap)."""
    return _cumulative(
        x, axis, dtype, include_initial,
        scan=_cumsum_backend, reduce_fn=_sum_with_dtype, identity=0,
    )


def cumulative_prod(x, /, *, axis=None, dtype=None, include_initial=False):
    """Cumulative product along ``axis`` (array-api 2023.12; reference gap)."""
    return _cumulative(
        x, axis, dtype, include_initial,
        scan=_cumprod_backend, reduce_fn=_prod_with_dtype, identity=1,
    )


def _check_quantile_args(x, q, fname):
    if not isinstance(q, (int, float)) or isinstance(q, bool):
        raise TypeError(f"{fname}: q must be a python float in [0, 1]")
    q = float(q)
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"{fname}: q must be in [0, 1]")
    if x.dtype not in _real_floating_dtypes:
        raise TypeError(
            f"Only real floating-point dtypes are allowed in {fname}"
        )
    return q


def _check_quantile_axis(x, axis, fname):
    if not -x.ndim <= axis < x.ndim:
        raise IndexError(
            f"{fname}: axis {axis} is out of bounds for array of "
            f"dimension {x.ndim}"
        )
    axis = axis % x.ndim
    if x.shape[axis] == 0:
        raise ValueError(f"{fname} of an empty axis")
    return axis


def quantile(x, q, /, *, axis=None, keepdims=False, method="linear"):
    """EXACT quantile along an axis — beyond both the standard and the
    reference (dask only approximates multi-chunk quantiles): the axis
    runs through the scale-out sort network (so it may exceed
    ``allowed_mem``), and the quantile is two STATIC slices of the sorted
    axis interpolated elementwise — no data-dependent shapes anywhere.

    ``q`` is a python float in [0, 1] (scalar only; map over floats for
    several). ``method``: "linear" (numpy default), "lower", "higher",
    "nearest"."""
    from .elementwise_functions import add, multiply
    from .manipulation_functions import flatten, squeeze
    from .sorting_functions import sort

    q = _check_quantile_args(x, q, "quantile")
    if method not in ("linear", "lower", "higher", "nearest"):
        raise ValueError(f"quantile: unsupported method {method!r}")

    if axis is None:
        flat = flatten(x)
        out = quantile(flat, q, axis=0, method=method)
        if keepdims:
            from .manipulation_functions import expand_dims

            for _ in range(x.ndim):
                out = expand_dims(out, axis=0)
        return out

    axis = _check_quantile_axis(x, axis, "quantile")
    n = x.shape[axis]

    pos = q * (n - 1)
    lo = int(np.floor(pos))
    hi = int(np.ceil(pos))
    frac = pos - lo
    if method == "lower":
        hi, frac = lo, 0.0
    elif method == "higher":
        lo, frac = hi, 0.0
    elif method == "nearest":
        lo = hi = int(round(pos))
        frac = 0.0

    s = sort(x, axis=axis)
    sel_lo = tuple(
        slice(lo, lo + 1) if d == axis else slice(None) for d in range(x.ndim)
    )
    out = s[sel_lo]
    if hi != lo:
        sel_hi = tuple(
            slice(hi, hi + 1) if d == axis else slice(None)
            for d in range(x.ndim)
        )
        from .creation_functions import asarray

        w = asarray(frac, dtype=x.dtype, spec=x.spec)
        one_minus = asarray(1.0 - frac, dtype=x.dtype, spec=x.spec)
        out = add(multiply(out, one_minus), multiply(s[sel_hi], w))

    # numpy semantics: any NaN along the axis poisons the quantile. sort
    # parks NaNs at the END of the axis, so the LAST element alone tells
    # whether any NaN exists — one static slice, not a second full pass
    from .creation_functions import asarray as _asarray
    from .elementwise_functions import isnan
    from .searching_functions import where

    sel_last = tuple(
        slice(n - 1, n) if d == axis else slice(None) for d in range(x.ndim)
    )
    has_nan = isnan(s[sel_last])
    out = where(has_nan, _asarray(float("nan"), dtype=x.dtype, spec=x.spec),
                out)
    return out if keepdims else squeeze(out, axis=axis)


def median(x, /, *, axis=None, keepdims=False):
    """Exact median via :func:`quantile` (q=0.5) — the sorted axis may
    exceed ``allowed_mem`` (sort network)."""
    return quantile(x, 0.5, axis=axis, keepdims=keepdims)


def histogram(x, /, *, bins=10, range=None, weights=None, density=False):
    """Chunked histogram (numpy semantics; no reference counterpart).

    Output shapes are STATIC: ``bins`` is an int (with optional
    ``range``) or an explicit edges sequence; when ``range`` is omitted
    the data min/max are computed lazily IN the plan (data-dependent
    values, never data-dependent shapes). Per-block partial counts sum
    through the reduction tree, so ``x`` may exceed ``allowed_mem``.
    Returns ``(counts, edges)``; ``weights``/``density`` as in numpy.

    Documented deviation: NaN data with an IMPLICIT range yields NaN
    edges (and meaningless counts) instead of numpy's runtime
    ValueError — a lazy plan cannot raise on data-dependent values.
    Pass an explicit ``range``/edges (numpy-identical semantics: NaNs
    fall outside every bin) or filter NaNs first."""
    from ..core.ops import general_blockwise
    from .creation_functions import arange, asarray
    from .data_type_functions import astype
    from .elementwise_functions import add, divide, greater, multiply, subtract
    from .manipulation_functions import flatten
    from .searching_functions import where
    from .utility_functions import diff

    if x.dtype not in _real_floating_dtypes:
        raise TypeError(
            "Only real floating-point dtypes are allowed in histogram"
        )
    flat = flatten(x)
    wflat = None
    if weights is not None:
        if weights.shape != x.shape:
            raise ValueError("histogram: weights must match x's shape")
        wflat = flatten(weights)
        if wflat.chunks != flat.chunks:
            wflat = wflat.rechunk(flat.chunksize)

    spec = x.spec
    if np.ndim(bins) == 0:
        nbins = int(bins)
        if nbins <= 0:
            raise ValueError("histogram: bins must be positive")
        if range is not None:
            lo_v, hi_v = float(range[0]), float(range[1])
            if not lo_v <= hi_v:
                raise ValueError("histogram: range must be increasing")
            if lo_v == hi_v:
                lo_v, hi_v = lo_v - 0.5, hi_v + 0.5
            # exact endpoints (numpy linspace semantics): the max sample
            # must land IN the closed last bin
            edges = asarray(
                np.linspace(lo_v, hi_v, nbins + 1), spec=spec
            )
        else:
            # lazy data extent in ONE pass over the data: a {lo, hi}
            # field tree (the mean/var pytree machinery) instead of two
            # independent min/max reductions
            from ..core.ops import _aggregate_fields, reduction_fields

            parts = reduction_fields(
                flat, _extent_func, _extent_combine, axis=(0,),
                fields={"lo": np.dtype(np.float64),
                        "hi": np.dtype(np.float64)},
            )
            names = ["lo", "hi"]
            f64 = np.dtype(np.float64)
            lo = _aggregate_fields(parts, _take_lo, f64, names)
            hi = _aggregate_fields(parts, _take_hi, f64, names)
            degenerate = greater(hi, lo)
            half = asarray(0.5, dtype=np.dtype(np.float64), spec=spec)
            lo = where(degenerate, lo, subtract(lo, half))
            hi = where(degenerate, hi, add(hi, half))
            # convex combination lo*(1-t) + hi*t with t = i/nbins: the
            # first/last edges equal lo/hi EXACTLY (a lo + i*step form
            # can round the last edge below the data max, dropping the
            # max sample from the closed last bin)
            t = divide(
                arange(nbins + 1, dtype=np.dtype(np.float64), spec=spec),
                asarray(float(nbins), dtype=np.dtype(np.float64), spec=spec),
            )
            one = asarray(1.0, dtype=np.dtype(np.float64), spec=spec)
            edges = add(
                multiply(lo, subtract(one, t)), multiply(hi, t)
            )
    else:
        edges_np = np.asarray(bins, dtype=np.float64)
        if edges_np.ndim != 1 or edges_np.size < 2:
            raise ValueError("histogram: bins edges must be 1-d with >= 2")
        if np.any(np.diff(edges_np) < 0):
            raise ValueError("histogram: bins edges must be monotonic")
        nbins = edges_np.size - 1
        edges = asarray(edges_np, spec=spec)

    if len(edges.chunks[0]) > 1:
        edges = edges.rechunk((nbins + 1,))

    nb = flat.numblocks[0]
    out_dtype = (
        np.dtype(np.float64) if wflat is not None or density
        else np.dtype(np.int64)
    )
    flat_name, edges_name = flat.name, edges.name
    w_name = wflat.name if wflat is not None else None

    def bf(out_key):
        i = out_key[1]
        keys = [(flat_name, i), (edges_name, 0)]
        if w_name is not None:
            keys.append((w_name, i))
        return tuple(keys)

    def _hist_block(xb, eb, *maybe_w):
        wb = maybe_w[0] if maybe_w else None
        counts, _ = nxp.histogram(xb, bins=eb, weights=wb)
        return nxp.reshape(counts.astype(out_dtype), (1, -1))

    args = [flat, edges] + ([wflat] if wflat is not None else [])
    partial = general_blockwise(
        _hist_block, bf, *args,
        shape=(nb, nbins),
        dtype=out_dtype,
        chunks=((1,) * nb, (nbins,)),
        op_name="histogram_partial",
    )
    counts = sum(partial, axis=0, dtype=out_dtype)

    if density:
        widths = diff(edges)
        total = sum(astype(counts, np.dtype(np.float64)))
        counts = divide(
            astype(counts, np.dtype(np.float64)), multiply(total, widths)
        )
    return counts, edges


def _extent_func(a, axis=None, keepdims=True, **kwargs):
    return {
        "lo": nxp.min(a, axis=axis, keepdims=keepdims).astype(np.float64),
        "hi": nxp.max(a, axis=axis, keepdims=keepdims).astype(np.float64),
    }


def _extent_combine(a, axis=None, keepdims=True, **kwargs):
    return {
        "lo": nxp.min(a["lo"], axis=axis, keepdims=keepdims),
        "hi": nxp.max(a["hi"], axis=axis, keepdims=keepdims),
    }


def _take_lo(d):
    return d["lo"]


def _take_hi(d):
    return d["hi"]


def cov(m, /, *, rowvar=True, ddof=1):
    """Covariance matrix of chunked observations (numpy semantics, no
    reference counterpart): centering + one blockwise contraction, so
    the observation axis may exceed ``allowed_mem``."""
    from .linear_algebra_functions import matmul, matrix_transpose

    if m.ndim != 2:
        raise ValueError("cov requires a 2-d array")
    if m.dtype not in _real_floating_dtypes:
        raise TypeError("Only real floating-point dtypes are allowed in cov")
    x = m if rowvar else matrix_transpose(m)
    n_obs = x.shape[1]
    if n_obs - ddof <= 0:
        raise ValueError("cov: not enough observations for ddof")
    centered = _subtract_mean(x, axis=1)
    from .elementwise_functions import divide
    from .creation_functions import asarray

    return divide(
        matmul(centered, matrix_transpose(centered)),
        asarray(float(n_obs - ddof), dtype=x.dtype, spec=x.spec),
    )


def _subtract_mean(x, axis):
    from .elementwise_functions import subtract

    m = mean(x, axis=axis, keepdims=True)
    return subtract(x, m)


def corrcoef(m, /, *, rowvar=True):
    """Correlation matrix from :func:`cov` (numpy semantics)."""
    from .elementwise_functions import clip, divide, sqrt
    from .linalg import diagonal

    c = cov(m, rowvar=rowvar, ddof=1)
    d = sqrt(diagonal(c))
    # rounding can push perfectly-correlated entries past 1; numpy clips
    return clip(divide(c, _outer_like(d)), min=-1.0, max=1.0)


def _outer_like(d):
    from .elementwise_functions import multiply
    from .manipulation_functions import expand_dims

    return multiply(expand_dims(d, axis=1), expand_dims(d, axis=0))


def nanquantile(x, q, /, *, axis=None, keepdims=False):
    """EXACT quantile ignoring NaNs (numpy.nanquantile semantics, linear
    interpolation). The sorted axis parks NaNs at the END, so the number
    of valid elements per lane gives COMPUTED gather indices — resolved
    with ``take_along_axis`` (chunked, memory-bounded) rather than static
    slices; all shapes stay static. All-NaN lanes yield NaN."""
    from .creation_functions import asarray
    from .data_type_functions import astype
    from .elementwise_functions import (
        add, floor, isnan, logical_not, multiply, subtract,
    )
    from .indexing_functions import take_along_axis
    from .manipulation_functions import expand_dims, flatten, squeeze
    from .searching_functions import where
    from .sorting_functions import sort

    q = _check_quantile_args(x, q, "nanquantile")
    if axis is None:
        out = nanquantile(flatten(x), q, axis=0)
        if keepdims:
            for _ in range(x.ndim):
                out = expand_dims(out, axis=0)
        return out

    axis = _check_quantile_axis(x, axis, "nanquantile")

    s = sort(x, axis=axis)
    # valid (non-NaN) count per lane, kept as a size-1 axis
    n_valid = sum(
        astype(logical_not(isnan(x)), np.dtype(np.int64)),
        axis=axis, keepdims=True,
    )
    nf = astype(n_valid, np.dtype(np.float64))
    qk = asarray(q, dtype=np.dtype(np.float64), spec=x.spec)
    one = asarray(1.0, dtype=np.dtype(np.float64), spec=x.spec)
    pos = multiply(qk, subtract(nf, one))          # q * (n_valid - 1)
    zero = asarray(0.0, dtype=np.dtype(np.float64), spec=x.spec)
    # n_valid == 0 gives pos = -q: clamp (the all-NaN overwrite below
    # decides the lane's value either way)
    pos = where(pos < zero, zero, pos)
    lo_f = floor(pos)
    frac = astype(subtract(pos, lo_f), x.dtype)
    lo_i = astype(lo_f, np.dtype(np.int64))
    hi_i = where(
        add(lo_i, asarray(1, dtype=np.dtype(np.int64), spec=x.spec))
        < n_valid,
        add(lo_i, asarray(1, dtype=np.dtype(np.int64), spec=x.spec)),
        lo_i,
    )
    # ONE streamed gather for both bounds (take_along_axis reads every
    # chunk of the sorted axis per output block; two calls would read
    # the whole sorted array twice)
    from .manipulation_functions import concat

    both = take_along_axis(s, concat([lo_i, hi_i], axis=axis), axis=axis)
    sel_lo = tuple(
        slice(0, 1) if d == axis else slice(None) for d in range(x.ndim)
    )
    sel_hi = tuple(
        slice(1, 2) if d == axis else slice(None) for d in range(x.ndim)
    )
    v_lo, v_hi = both[sel_lo], both[sel_hi]
    out = add(
        multiply(v_lo, subtract(asarray(1.0, dtype=x.dtype, spec=x.spec),
                                frac)),
        multiply(v_hi, frac),
    )
    # all-NaN lanes: no valid data -> NaN
    nan_c = asarray(float("nan"), dtype=x.dtype, spec=x.spec)
    out = where(
        n_valid < asarray(1, dtype=np.dtype(np.int64), spec=x.spec),
        nan_c, out,
    )
    return out if keepdims else squeeze(out, axis=axis)


def nanmedian(x, /, *, axis=None, keepdims=False):
    """Exact median ignoring NaNs (see :func:`nanquantile`)."""
    return nanquantile(x, 0.5, axis=axis, keepdims=keepdims)
