"""Aggregation hot-path kernels: segmented group-by reduce, windowed
reductions, histogram — the TPU-era stand-ins for SAGE's in-storage
compute primitives (paper §4.1: the reductions its Data Analytics
layer runs next to the data).

Layout follows the percipience heat-scan idiom (percipience/heat.py):
inputs are padded to f32/int32 tile multiples (8, 128), and CPU
containers run the same kernel body with ``interpret=True``.  A
pure-numpy reference implementation backs every kernel for correctness
checks and as the no-JAX fallback.

Segmented reduce: values live in a (rows, 128)-lane layout.  The grid is
(segment blocks, row blocks): the segment axis is parallel, each step
owning 128 segments; the row axis is sequential ("arbitrary") over
fixed ``_ROW_BLOCK``-row input blocks, with the accumulators kept in the
output block, which stays resident in VMEM across the row axis.  Each
loop step reads one row from the input ref, moves it onto sublanes, and
folds it in with a lane-iota membership mask — a (128 values x 128
segments) compare + masked reduce per row, all VPU work.  Integer inputs
reduce in int32 so integer aggregates are *exact* (no f32 rounding),
matching the numpy reference bit-for-bit.

Windowed reduce: values arranged (window, n_windows) — window axis on
sublanes, windows on lanes — one column reduce per 128-window block,
the same shape trick the heat kernel uses for (hist, nobj).
"""
from __future__ import annotations

import functools
import json
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analytics.exprs import _BINOPS
from repro.core.addb import span
from repro.percipience.heat import _heat_call

OPS = ("sum", "count", "min", "max")
_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES
# rows of 128 lanes per input block on the sequential row axis: 512 KiB
# per int32/f32 column block, so a fused call over 3 columns + ids stays
# near 4 MiB of VMEM double-buffered, whatever the partition size
_ROW_BLOCK = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_mode(interpret: bool = False) -> str:
    """How a kernel call will actually execute: ``pallas-tpu`` (compiled
    Mosaic), ``xla-jit`` (compiled XLA fallback — the honest CPU path),
    or ``interpret`` (Pallas interpreter; correctness only, never
    timing).  Benchmarks label every number with this."""
    if interpret:
        return "interpret"
    return "pallas-tpu" if _on_tpu() else "xla-jit"


def _identity(op: str, dtype) -> float:
    if op in ("sum", "count"):
        return 0
    big = np.iinfo(dtype).max if np.issubdtype(dtype, np.integer) \
        else np.inf
    return big if op == "min" else -big


# ---------------------------------------------------------------------------
# expression-spec evaluation (shared by the fused kernel + XLA fallback)
# ---------------------------------------------------------------------------

def eval_spec(spec: Dict, getcol):
    """Evaluate a serialised expression spec (exprs.to_spec) against
    ``getcol(i) -> array``.  The operator table is generic, so the same
    walker runs on numpy arrays (host reference), jnp arrays (XLA
    fallback) and Pallas block values (fused kernel body)."""
    t = spec["t"]
    if t == "col":
        return getcol(spec["i"])
    if t == "lit":
        return spec["v"]
    if t == "bin":
        return _BINOPS[spec["op"]](eval_spec(spec["l"], getcol),
                                   eval_spec(spec["r"], getcol))
    if t == "not":
        return ~eval_spec(spec["e"], getcol)
    raise ValueError(f"bad expr spec {spec!r}")


def spec_columns(spec: Optional[Dict]) -> set:
    """Column indices a spec reads (pruned-scan planning)."""
    if spec is None:
        return set()
    t = spec["t"]
    if t == "col":
        return {spec["i"]}
    if t == "bin":
        return spec_columns(spec["l"]) | spec_columns(spec["r"])
    if t == "not":
        return spec_columns(spec["e"])
    return set()


_CMP_OPS = (">", ">=", "<", "<=", "==", "!=")


def _spec_dtype(spec: Dict, coldt: Dict[int, np.dtype]) -> np.dtype:
    """Result dtype of a spec under numpy promotion — how the unfused
    path's ``expr(rows)`` would come out, so the fused kernel picks the
    identical int32/float32 accumulator."""
    t = spec["t"]
    if t == "col":
        return np.dtype(coldt[spec["i"]])
    if t == "lit":
        return np.asarray(spec["v"]).dtype
    if t == "not":
        return np.dtype(bool)
    if spec["op"] in _CMP_OPS:
        return np.dtype(bool)
    l = _spec_dtype(spec["l"], coldt)
    r = _spec_dtype(spec["r"], coldt)
    if spec["op"] == "/":
        return np.result_type(l, r, np.float32)
    return np.result_type(l, r)


# ---------------------------------------------------------------------------
# segmented group-by reduce
# ---------------------------------------------------------------------------

def _row_block(rows: int) -> int:
    """Rows per input block: the whole (8-row aligned) array when it is
    small, else ``_ROW_BLOCK`` (callers pad rows to a multiple)."""
    return min(rows, _ROW_BLOCK)


def _padded_size(n: int) -> int:
    """Element count padded to whole (8, 128) tiles, and to whole row
    blocks once the array spans more than one."""
    unit = _TILE if n <= _ROW_BLOCK * _LANES else _ROW_BLOCK * _LANES
    return -(-n // unit) * unit


def _sublane_row(ref, r):
    """Row ``r`` of a (rows, 128) ref, read from the ref and moved onto
    sublanes as (128, 1) so it broadcasts against the segment lanes."""
    return ref[pl.ds(r, 1), :].reshape(_LANES, 1)


def _fold(mask, acc, v, op: str, ident):
    """Fold one row's (128 values x 128 segments) membership mask into
    the (1, 128) segment accumulator; ``v`` is the (128, 1) value row."""
    if op == "count":
        return acc + jnp.sum(mask.astype(acc.dtype), axis=0, keepdims=True)
    if op == "sum":
        return acc + jnp.sum(jnp.where(mask, v, 0), axis=0, keepdims=True)
    if op == "min":
        return jnp.minimum(acc, jnp.min(jnp.where(mask, v, ident), axis=0,
                                        keepdims=True))
    return jnp.maximum(acc, jnp.max(jnp.where(mask, v, ident), axis=0,
                                    keepdims=True))


def _segment_lanes():
    """(128, 128) segment id of each lane in this grid step's block."""
    return pl.program_id(0) * _LANES + \
        jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)


def _segment_kernel(v_ref, id_ref, out_ref, *, block_rows: int, op: str,
                    ident):
    """v, id: (block_rows, 128) value/segment-id lanes of this row block;
    out: (1, 128) — the running reduction of each segment in this grid
    step's 128-segment block, resident across the row axis."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, ident)

    segs = _segment_lanes()

    def body(r, acc):                       # acc: (1, 128)
        mask = _sublane_row(id_ref, r) == segs   # (128 values, 128 segs)
        return _fold(mask, acc, _sublane_row(v_ref, r), op, ident)

    out_ref[...] = jax.lax.fori_loop(0, block_rows, body, out_ref[...])


@functools.lru_cache(maxsize=512)
def _segment_call(rows: int, n_seg_blocks: int, op: str, dtype_name: str,
                  interpret: bool):
    """Jitted pallas_call for one (tile shape, op, dtype) — cached so
    per-partition calls with a recurring padded shape stop retracing."""
    ident = _identity(op, np.dtype(dtype_name))
    rb = _row_block(rows)
    kernel = functools.partial(_segment_kernel, block_rows=rb, op=op,
                               ident=ident)
    call = pl.pallas_call(
        kernel,
        grid=(n_seg_blocks, rows // rb),
        in_specs=[
            pl.BlockSpec((rb, _LANES), lambda s, r: (r, 0)),
            pl.BlockSpec((rb, _LANES), lambda s, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((1, _LANES), lambda s, r: (0, s)),
        out_shape=jax.ShapeDtypeStruct((1, n_seg_blocks * _LANES),
                                       np.dtype(dtype_name)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sage_segment_reduce",
    )

    def sage_segment_reduce(values, seg_ids):
        return call(values, seg_ids)
    return jax.jit(sage_segment_reduce)


def segment_reduce_pallas(values: jax.Array, seg_ids: jax.Array,
                          n_seg_blocks: int, *, op: str,
                          interpret: bool = False) -> jax.Array:
    """values: (rows, 128) f32/int32; seg_ids: (rows, 128) int32 with -1
    marking padding lanes; rows a multiple of 8, and of ``_ROW_BLOCK``
    when larger.  Returns (1, n_seg_blocks * 128) reduced values
    (identity where a segment saw no members)."""
    rows, lanes = values.shape
    assert lanes == _LANES and rows % _row_block(rows) == 0 \
        and rows % _SUBLANES == 0
    call = _segment_call(rows, n_seg_blocks, op,
                         np.dtype(values.dtype).name, interpret)
    return call(values, seg_ids)


@functools.lru_cache(maxsize=512)
def _xla_segment_call(op: str, dtype_name: str, n_segments: int):
    """Compiled XLA segmented reduce — the honest non-interpret CPU
    path.  Negative ids route to a dump bucket past the real segments;
    jax.ops.segment_* fill empty segments with the exact op identities
    (0 / iinfo extremes / ±inf), matching ``_identity``."""
    def run(v, ids):
        idx = jnp.where(ids >= 0, ids, n_segments)
        if op == "sum":
            out = jax.ops.segment_sum(v, idx, num_segments=n_segments + 1)
        elif op == "count":
            out = jax.ops.segment_sum(jnp.ones_like(v), idx,
                                      num_segments=n_segments + 1)
        elif op == "min":
            out = jax.ops.segment_min(v, idx, num_segments=n_segments + 1)
        else:
            out = jax.ops.segment_max(v, idx, num_segments=n_segments + 1)
        return out[:n_segments]
    return jax.jit(run)


def segment_reduce(values: np.ndarray, seg_ids: np.ndarray, n_segments: int,
                   *, op: str = "sum",
                   interpret: bool = False) -> np.ndarray:
    """Reduce ``values`` by integer segment id in [0, n_segments).

    Negative ids are dropped.  Integer inputs reduce in int32 (exact);
    everything else in float32.  Returns (n_segments,) with the op
    identity for empty segments.  Off TPU with ``interpret=False`` the
    reduction runs as compiled XLA (``kernel_mode``); ``interpret=True``
    forces the Pallas interpreter (bit-parity with the TPU kernel).
    """
    if op not in OPS:
        raise ValueError(f"op must be one of {OPS}")
    v = np.asarray(values).reshape(-1)
    ids = np.asarray(seg_ids, np.int32).reshape(-1)
    if v.shape != ids.shape:
        raise ValueError("values and seg_ids must align")
    dtype = np.int32 if np.issubdtype(v.dtype, np.integer) else np.float32
    if n_segments <= 0 or v.size == 0:
        return np.full((max(n_segments, 0),),
                       _identity(op, np.dtype(dtype)), dtype)
    v = v.astype(dtype)
    ident = _identity(op, np.dtype(dtype))

    n = v.size
    pad = _padded_size(n) - n
    if pad:
        v = np.pad(v, (0, pad), constant_values=dtype(0) if op in
                   ("sum", "count") else ident)
        ids = np.pad(ids, (0, pad), constant_values=-1)

    mode = kernel_mode(interpret)
    if mode == "xla-jit":
        call = _xla_segment_call(op, np.dtype(dtype).name, n_segments)
        return np.asarray(call(jnp.asarray(v), jnp.asarray(ids)))

    vm = v.reshape(-1, _LANES)
    im = ids.reshape(-1, _LANES)
    n_seg_blocks = -(-n_segments // _LANES)
    out = np.asarray(segment_reduce_pallas(
        jnp.asarray(vm), jnp.asarray(im), n_seg_blocks, op=op,
        interpret=mode == "interpret"))
    return out[0, :n_segments]


def segment_reduce_ref(values: np.ndarray, seg_ids: np.ndarray,
                       n_segments: int, *, op: str = "sum") -> np.ndarray:
    """Pure-numpy reference (np.ufunc.at scatter)."""
    v = np.asarray(values).reshape(-1)
    ids = np.asarray(seg_ids, np.int64).reshape(-1)
    dtype = np.int32 if np.issubdtype(v.dtype, np.integer) else np.float32
    v = v.astype(dtype)
    keep = ids >= 0
    v, ids = v[keep], ids[keep]
    out = np.full((n_segments,), _identity(op, np.dtype(dtype)), dtype)
    if op == "sum":
        np.add.at(out, ids, v)
    elif op == "count":
        np.add.at(out, ids, np.ones_like(v, dtype))
    elif op == "min":
        np.minimum.at(out, ids, v)
    else:
        np.maximum.at(out, ids, v)
    return out


# ---------------------------------------------------------------------------
# windowed reductions
# ---------------------------------------------------------------------------

def _window_kernel(v_ref, out_ref, *, op: str):
    """v: (window, wb) — window axis on sublanes; out: (1, wb)."""
    v = v_ref[...]
    if op in ("sum", "count"):
        out_ref[...] = jnp.sum(v, axis=0, keepdims=True)
    elif op == "min":
        out_ref[...] = jnp.min(v, axis=0, keepdims=True)
    else:
        out_ref[...] = jnp.max(v, axis=0, keepdims=True)


@functools.lru_cache(maxsize=512)
def _window_call(w: int, nw: int, op: str, dtype_name: str,
                 interpret: bool):
    kernel = functools.partial(_window_kernel, op=op)
    call = pl.pallas_call(
        kernel,
        grid=(nw // _LANES,),
        in_specs=[pl.BlockSpec((w, _LANES), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, _LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nw), np.dtype(dtype_name)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sage_window_reduce",
    )

    def sage_window_reduce(vt):
        return call(vt)
    return jax.jit(sage_window_reduce)


def window_reduce_pallas(vt: jax.Array, *, op: str,
                         interpret: bool = False) -> jax.Array:
    """vt: (window, n_windows) with window % 8 == 0, n_windows % 128 == 0.
    Returns (1, n_windows)."""
    w, nw = vt.shape
    assert w % _SUBLANES == 0 and nw % _LANES == 0
    call = _window_call(w, nw, op, np.dtype(vt.dtype).name, interpret)
    return call(vt)


@functools.lru_cache(maxsize=512)
def _xla_window_call(op: str, dtype_name: str):
    def run(mat):                            # (n_windows, window)
        if op in ("sum", "count"):
            return jnp.sum(mat, axis=1)
        if op == "min":
            return jnp.min(mat, axis=1)
        return jnp.max(mat, axis=1)
    return jax.jit(run)


def _window_matrix(values: np.ndarray, window: int, slide: int
                   ) -> np.ndarray:
    """(n_windows, window) matrix of full windows (tail dropped)."""
    if window <= 0 or slide <= 0:
        raise ValueError("window size and slide must be positive")
    v = np.asarray(values).reshape(-1)
    if v.size < window:
        return v[:0].reshape(0, window)
    n_windows = (v.size - window) // slide + 1
    idx = (np.arange(n_windows)[:, None] * slide +
           np.arange(window)[None, :])
    return v[idx]


def window_reduce(values: np.ndarray, window: int, *, op: str = "sum",
                  slide: Optional[int] = None,
                  interpret: bool = False) -> np.ndarray:
    """Tumbling (or, with ``slide``, sliding) window reduction over a 1-D
    value sequence; only complete windows emit.  ``mean`` callers divide
    the ``sum`` result by ``window``."""
    if op not in OPS:
        raise ValueError(f"op must be one of {OPS}")
    slide = window if slide is None else slide
    mat = _window_matrix(values, window, slide)
    if mat.shape[0] == 0:
        return np.zeros((0,), np.float32)
    dtype = np.int32 if np.issubdtype(mat.dtype, np.integer) else np.float32
    mat = mat.astype(dtype)
    if op == "count":
        mat = np.ones_like(mat)
    ident = _identity(op, np.dtype(dtype))

    mode = kernel_mode(interpret)
    if mode == "xla-jit":
        call = _xla_window_call(op, np.dtype(dtype).name)
        return np.asarray(call(jnp.asarray(mat)))

    vt = np.ascontiguousarray(mat.T)          # (window, n_windows)
    w, nw = vt.shape
    pw, pn = (-w) % _SUBLANES, (-nw) % _LANES
    if pw or pn:
        fill = dtype(0) if op in ("sum", "count") else ident
        vt = np.pad(vt, ((0, pw), (0, pn)), constant_values=fill)
    out = np.asarray(window_reduce_pallas(
        jnp.asarray(vt), op=op, interpret=mode == "interpret"))
    return out[0, :nw]


def window_reduce_ref(values: np.ndarray, window: int, *, op: str = "sum",
                      slide: Optional[int] = None) -> np.ndarray:
    slide = window if slide is None else slide
    mat = _window_matrix(values, window, slide)
    dtype = np.int32 if np.issubdtype(mat.dtype, np.integer) else np.float32
    mat = mat.astype(dtype)
    if mat.shape[0] == 0:
        return np.zeros((0,), np.float32)
    fn = {"sum": np.sum, "count": np.sum, "min": np.min, "max": np.max}[op]
    if op == "count":
        mat = np.ones_like(mat)
    return fn(mat, axis=1)


# ---------------------------------------------------------------------------
# histogram (fixed uniform bins -> segmented count)
# ---------------------------------------------------------------------------

def histogram_bin_ids(values: np.ndarray, bins: int,
                      vrange: Tuple[float, float]) -> np.ndarray:
    """Uniform-bin ids with np.histogram edge semantics: values in
    [lo, hi], hi landing in the last bin; out-of-range -> -1 (dropped)."""
    lo, hi = float(vrange[0]), float(vrange[1])
    if not (bins > 0 and lo < hi):
        raise ValueError("histogram needs bins > 0 and vrange lo < hi")
    v = np.asarray(values, np.float64).reshape(-1)
    width = (hi - lo) / bins
    ids = np.floor((v - lo) / width).astype(np.int64)
    ids = np.minimum(ids, bins - 1)           # v == hi -> last bin
    ids[(v < lo) | (v > hi)] = -1
    return ids


def histogram(values: np.ndarray, bins: int, vrange: Tuple[float, float],
              *, interpret: bool = False) -> np.ndarray:
    """np.histogram-compatible uniform-bin counts via the segmented
    count kernel."""
    ids = histogram_bin_ids(values, bins, vrange)
    ones = np.ones(ids.shape, np.int32)
    return segment_reduce(ones, ids, bins, op="count", interpret=interpret)


def histogram_ref(values: np.ndarray, bins: int,
                  vrange: Tuple[float, float]) -> np.ndarray:
    return np.histogram(np.asarray(values).reshape(-1), bins=bins,
                        range=vrange)[0].astype(np.int32)


# ---------------------------------------------------------------------------
# fused filter -> segmented reduce
# ---------------------------------------------------------------------------
#
# The pushdown hot path: evaluate the shipped predicate AND fold the
# survivors into segment accumulators in one pass over the tiled block —
# no materialized boolean mask, no compacted intermediate rows.  Inputs
# arrive as individual column lanes (the colblock pruned-read shape), a
# predicate/value expression spec each, and host-computed segment ids
# for the *unfiltered* rows; rejected rows simply never match a segment
# lane.  Each call also returns per-segment survivor counts so the
# caller can drop empty groups (keeping group keys identical to the
# unfused filter-then-unique path) and derive means.

def _fused_kernel(*refs, ncols: int, order: Tuple[int, ...],
                  block_rows: int, op: str, ident,
                  pred_spec: Optional[Dict], value_spec: Optional[Dict],
                  out_dtype):
    """refs: ncols column blocks (block_rows, 128), then ids
    (block_rows, 128), then acc (1, 128) and count (1, 128) outputs for
    this grid step's 128-segment block, resident across the row axis.
    Each loop step reads one row of every column from its ref and
    evaluates the predicate and value on that (1, 128) row."""
    col_refs = {orig: refs[j] for j, orig in enumerate(order)}
    id_ref, acc_ref, cnt_ref = refs[ncols], refs[ncols + 1], refs[ncols + 2]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ident)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    segs = _segment_lanes()

    def body(r, carry):                      # carry: ((1,128), (1,128))
        acc, cnt = carry
        row = pl.ds(r, 1)
        getcol = lambda i: col_refs[i][row, :]       # noqa: E731
        ids = id_ref[row, :]
        if pred_spec is not None:
            keep = jnp.broadcast_to(
                jnp.asarray(eval_spec(pred_spec, getcol), jnp.bool_),
                ids.shape)
            # padding lanes carry ids == -1, so they never match a segment
            ids = jnp.where(keep, ids, -1)
        if value_spec is None:
            val = jnp.ones(ids.shape, out_dtype)
        else:
            val = jnp.broadcast_to(
                jnp.asarray(eval_spec(value_spec, getcol)).astype(out_dtype),
                ids.shape)
        mask = ids.reshape(_LANES, 1) == segs    # (128 rows, 128 segments)
        cnt = cnt + jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)
        return _fold(mask, acc, val.reshape(_LANES, 1), op, ident), cnt

    acc, cnt = jax.lax.fori_loop(0, block_rows, body,
                                 (acc_ref[...], cnt_ref[...]))
    acc_ref[...] = acc
    cnt_ref[...] = cnt


@functools.lru_cache(maxsize=512)
def _fused_pallas_call(rows: int, n_seg_blocks: int, op: str,
                       dtype_name: str, pred_json: str, value_json: str,
                       order: Tuple[int, ...], interpret: bool):
    dtype = np.dtype(dtype_name)
    ncols = len(order)
    rb = _row_block(rows)
    kernel = functools.partial(
        _fused_kernel, ncols=ncols, order=order, block_rows=rb, op=op,
        ident=_identity(op, dtype),
        pred_spec=json.loads(pred_json) if pred_json else None,
        value_spec=json.loads(value_json) if value_json else None,
        out_dtype=dtype)
    call = pl.pallas_call(
        kernel,
        grid=(n_seg_blocks, rows // rb),
        in_specs=[pl.BlockSpec((rb, _LANES), lambda s, r: (r, 0))
                  for _ in range(ncols + 1)],
        out_specs=[pl.BlockSpec((1, _LANES), lambda s, r: (0, s)),
                   pl.BlockSpec((1, _LANES), lambda s, r: (0, s))],
        out_shape=[jax.ShapeDtypeStruct((1, n_seg_blocks * _LANES), dtype),
                   jax.ShapeDtypeStruct((1, n_seg_blocks * _LANES),
                                        np.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sage_fused_filter_agg",
    )

    def sage_fused_filter_agg(*cols_and_ids):
        return call(*cols_and_ids)
    return jax.jit(sage_fused_filter_agg)


_XLA_FOLD_SEGMENTS = 64            # membership-fold beats scatter below this
_XLA_FOLD_CHUNK = 1 << 13          # rows per scan step (fits L2 with mask)


@functools.lru_cache(maxsize=512)
def _fused_xla_call(op: str, dtype_name: str, n_segments: int,
                    pred_json: str, value_json: str,
                    order: Tuple[int, ...]):
    """Compiled XLA fusion for the non-TPU path: predicate + value +
    segmented reduce in one jitted program.  Small segment counts run
    the same membership fold the Pallas kernel uses — a streaming
    chunked pass carrying one accumulator lane per segment, no scatter
    and no materialised mask; larger counts fall back to XLA's segment
    scatter with a dump bucket for rejected/padding rows."""
    pred_spec = json.loads(pred_json) if pred_json else None
    value_spec = json.loads(value_json) if value_json else None
    dtype = np.dtype(dtype_name)
    ident = _identity(op, dtype)

    def _eval(ids, colarrs):
        cols = {orig: colarrs[j] for j, orig in enumerate(order)}
        if pred_spec is None:
            keep = ids >= 0
        else:
            keep = eval_spec(pred_spec, lambda i: cols[i])
            keep = jnp.broadcast_to(jnp.asarray(keep, jnp.bool_),
                                    ids.shape) & (ids >= 0)
        if value_spec is None:
            val = jnp.ones(ids.shape, dtype)
        else:
            val = eval_spec(value_spec, lambda i: cols[i])
            val = jnp.broadcast_to(jnp.asarray(val).astype(dtype),
                                   ids.shape)
        return keep, val

    def _fold(ids, colarrs, acc, cnt):
        keep, val = _eval(ids, colarrs)
        ids_eff = jnp.where(keep, ids, -1)
        m = ids_eff[:, None] == jnp.arange(n_segments,
                                           dtype=jnp.int32)[None, :]
        mv = jnp.where(m, val[:, None], jnp.asarray(ident, dtype))
        if op in ("sum", "count"):
            acc = acc + jnp.sum(mv, axis=0)
        elif op == "min":
            acc = jnp.minimum(acc, jnp.min(mv, axis=0))
        else:
            acc = jnp.maximum(acc, jnp.max(mv, axis=0))
        return acc, cnt + jnp.sum(m, axis=0, dtype=jnp.int32)

    def run(ids, *colarrs):
        if n_segments <= _XLA_FOLD_SEGMENTS:
            n, ch = ids.shape[0], _XLA_FOLD_CHUNK
            acc = jnp.full((n_segments,), ident, dtype)
            cnt = jnp.zeros((n_segments,), jnp.int32)
            main = (n // ch) * ch
            if main:
                def body(carry, xs):
                    return _fold(xs[0], xs[1:], *carry), None
                xs = (ids[:main].reshape(-1, ch),) + tuple(
                    c[:main].reshape(-1, ch) for c in colarrs)
                (acc, cnt), _ = jax.lax.scan(body, (acc, cnt), xs)
            if n > main:
                acc, cnt = _fold(ids[main:],
                                 [c[main:] for c in colarrs], acc, cnt)
            return acc, cnt
        keep, val = _eval(ids, colarrs)
        idx = jnp.where(keep, ids, n_segments)
        seg = {"sum": jax.ops.segment_sum, "count": jax.ops.segment_sum,
               "min": jax.ops.segment_min, "max": jax.ops.segment_max}[op]
        acc = seg(val, idx, num_segments=n_segments + 1)
        cnt = jax.ops.segment_sum(keep.astype(jnp.int32), idx,
                                  num_segments=n_segments + 1)
        return acc[:n_segments], cnt[:n_segments]
    return jax.jit(run)


def fused_out_dtype(value_spec: Optional[Dict],
                    coldt: Dict[int, np.dtype]) -> np.dtype:
    """int32/float32 accumulator choice, identical to what the unfused
    path gets from evaluating the value expr on numpy rows."""
    if value_spec is None:
        return np.dtype(np.int32)            # count's ones
    dt = _spec_dtype(value_spec, coldt)
    return np.dtype(np.int32) if np.issubdtype(dt, np.integer) \
        else np.dtype(np.float32)


def fused_filter_aggregate(cols: Dict[int, np.ndarray],
                           pred_spec: Optional[Dict],
                           value_spec: Optional[Dict],
                           seg_ids: np.ndarray, n_segments: int, *,
                           op: str, interpret: bool = False,
                           out_dtype=None
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """One-pass filter -> segmented reduce over column arrays.

    ``cols`` maps original column index -> (rows,) array (a pruned
    colblock read or sliced row-major block); ``seg_ids`` are
    host-computed int32 ids in [0, n_segments) over the *unfiltered*
    rows (-1 drops a row unconditionally).  Returns
    ``(agg, counts)`` of shape (n_segments,): the op-reduced survivor
    values (op identity where no survivors) and survivor counts.
    Integer aggregates are exact int32 — bit-identical to the unfused
    mask-then-reduce path on every backend.  ``out_dtype`` overrides the
    inferred int32/float32 accumulator (grouped means reduce integer
    values in float32, matching the unfused cast-then-reduce).
    """
    if op not in OPS:
        raise ValueError(f"op must be one of {OPS}")
    ids = np.asarray(seg_ids, np.int32).reshape(-1)
    n = ids.size
    order = tuple(sorted(cols))
    coldt = {i: np.asarray(cols[i]).dtype for i in order}
    dtype = np.dtype(out_dtype) if out_dtype is not None \
        else fused_out_dtype(value_spec, coldt)
    ident = _identity(op, dtype)
    if n_segments <= 0 or n == 0:
        return (np.full((max(n_segments, 0),), ident, dtype),
                np.zeros((max(n_segments, 0),), np.int32))

    pred_json = json.dumps(pred_spec, sort_keys=True) if pred_spec else ""
    value_json = json.dumps(value_spec, sort_keys=True) if value_spec \
        else ""
    mode = kernel_mode(interpret)

    with span("sage.kernel.put", "h2d_s"):
        pad = _padded_size(n) - n
        ids_p = np.pad(ids, (0, pad), constant_values=-1) if pad else ids
        col_p = []
        for i in order:
            c = np.asarray(cols[i]).reshape(-1)
            if c.size != n:
                raise ValueError(f"column {i} has {c.size} rows, ids {n}")
            # pad value 1 keeps pad-lane predicate math away from div-by-zero
            col_p.append(np.pad(c, (0, pad), constant_values=c.dtype.type(1))
                         if pad else c)
        if mode == "xla-jit":
            args = [jnp.asarray(ids_p)] + [jnp.asarray(c) for c in col_p]
        else:
            args = [jnp.asarray(c.reshape(-1, _LANES)) for c in col_p] + [
                jnp.asarray(ids_p.reshape(-1, _LANES))]

    # the host waits here for the kernel and the copy back
    with span("sage.kernel.wait", "kernel_s"):
        if mode == "xla-jit":
            call = _fused_xla_call(op, dtype.name, n_segments, pred_json,
                                   value_json, order)
            acc, cnt = call(*args)
            return np.asarray(acc), np.asarray(cnt)
        n_seg_blocks = -(-n_segments // _LANES)
        call = _fused_pallas_call(ids_p.size // _LANES, n_seg_blocks, op,
                                  dtype.name, pred_json, value_json, order,
                                  mode == "interpret")
        acc, cnt = call(*args)
        return (np.asarray(acc)[0, :n_segments],
                np.asarray(cnt)[0, :n_segments])


def fused_filter_aggregate_ref(cols: Dict[int, np.ndarray],
                               pred_spec: Optional[Dict],
                               value_spec: Optional[Dict],
                               seg_ids: np.ndarray, n_segments: int, *,
                               op: str) -> Tuple[np.ndarray, np.ndarray]:
    """Pure-numpy reference: materialize the mask, compact, reduce —
    exactly the unfused path the fused kernel must match."""
    ids = np.asarray(seg_ids, np.int64).reshape(-1)
    order = tuple(sorted(cols))
    coldt = {i: np.asarray(cols[i]).dtype for i in order}
    dtype = fused_out_dtype(value_spec, coldt)
    getcol = lambda i: np.asarray(cols[i]).reshape(-1)   # noqa: E731
    if pred_spec is None:
        keep = ids >= 0
    else:
        keep = np.broadcast_to(
            np.asarray(eval_spec(pred_spec, getcol), bool),
            ids.shape) & (ids >= 0)
    if value_spec is None:
        val = np.ones(ids.shape, dtype)
    else:
        val = np.broadcast_to(
            np.asarray(eval_spec(value_spec, getcol)).astype(dtype),
            ids.shape)
    ids_k, val_k = ids[keep], val[keep]
    acc = segment_reduce_ref(val_k.astype(dtype), ids_k, n_segments, op=op)
    cnt = segment_reduce_ref(np.ones(ids_k.shape, np.int32), ids_k,
                             n_segments, op="count")
    return acc.astype(dtype), cnt


# ---------------------------------------------------------------------------
# kernel-closure cache introspection
# ---------------------------------------------------------------------------

_CACHED_BUILDERS = (_segment_call, _xla_segment_call, _window_call,
                    _xla_window_call, _fused_pallas_call, _fused_xla_call,
                    _heat_call)


def kernel_cache_info() -> Dict[str, int]:
    """Aggregate hit/miss/entry counts over every cached jitted-kernel
    builder — a miss is one trace+compile; hits reuse the closure."""
    hits = misses = entries = 0
    for b in _CACHED_BUILDERS:
        ci = b.cache_info()
        hits, misses, entries = (hits + ci.hits, misses + ci.misses,
                                 entries + ci.currsize)
    return {"hits": hits, "misses": misses, "entries": entries}


def kernel_cache_clear():
    for b in _CACHED_BUILDERS:
        b.cache_clear()
