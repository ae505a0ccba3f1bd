"""Pallas TPU kernel: segment reductions for grouped aggregates.

The compiled-plan executor (``core/compiled.py``) lowers a grouped
COUNT/SUM/AVG/MIN/MAX to a segment reduction over the group-id column
(``inv`` from ``np.unique(keys, return_inverse=True)``).  The host NumPy
path in ``kernels.ops.segment_reduce`` stays the bit-exact oracle; this
kernel is the device path (``QUIP_SEGMENT_IMPL=pallas``).

Shape strategy: rows run along the 128-wide lane axis, one ``(1, RB)``
block of values and segment ids per grid step (a 1-D ``(RB,)`` block does
not match the TPU's tiled layout), and segments run along sublanes in
tiles of ``TS``.  The grid is ``(segment tiles, row blocks)``: each step
builds a ``(TS, RB)`` one-hot match of its segment ids against the tile's
class iota, folds the masked values lane-group by lane-group into a
``(TS, 128)`` partial, and combines that into the output block, which
stays resident across the row blocks (the grid is sequential).  The
wrapper reduces the 128 lanes of each segment's partial.  Tiling the
segments keeps VMEM use independent of the group count; the work is
still O(rows × groups).  Padded rows carry segment id −1,
which matches no class; padded segments are sliced off by the wrapper.

``op`` is static: ``sum`` accumulates ``+``, ``min``/``max`` accumulate
``jnp.minimum``/``maximum`` with the dtype identity as the initial fill
(count is a sum of ones, handled by the wrapper).  Empty segments hold
the identity; callers mask them via the count op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["segment_reduce_pallas"]

RB = 512  # rows per block (lanes)
LANE = 128
TS = 256  # segments per tile (sublanes)
SUB = 8

_OPS = ("sum", "min", "max")
_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
_REDUCE = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def _pad_axis(x, mult, axis, value):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _identity(op: str, dtype) -> jnp.ndarray:
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.array(info.max if op == "min" else info.min, dtype)
    return jnp.array(jnp.inf if op == "min" else -jnp.inf, dtype)


def _segment_kernel(vals_ref, seg_ref, out_ref, *, op: str):
    seg = seg_ref[...]  # (1, RB) int32; pad rows are -1
    vals = vals_ref[...]  # (1, RB)
    ts = out_ref.shape[0]
    classes = (jax.lax.broadcasted_iota(jnp.int32, (ts, RB), 0)
               + pl.program_id(0) * ts)
    ident = _identity(op, vals.dtype)
    masked = jnp.where(seg == classes, vals, ident)  # (TS, RB)
    combine = _COMBINE[op]
    part = masked[:, :LANE]
    for j in range(1, RB // LANE):
        part = combine(part, masked[:, j * LANE:(j + 1) * LANE])

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, ident, out_ref.dtype)

    out_ref[...] = combine(out_ref[...], part)


@functools.partial(jax.jit, static_argnames=("num_segments", "op", "interpret"))
def segment_reduce_pallas(
    vals: jnp.ndarray,
    seg_ids: jnp.ndarray,
    *,
    num_segments: int,
    op: str,
    interpret: bool,
) -> jnp.ndarray:
    """(n,) values + (n,) int32 segment ids → (num_segments,) reduction.

    Negative segment ids drop the row (the wrapper pads with −1).
    """
    if op not in _OPS:
        raise ValueError(f"unknown segment op {op!r}")
    ident = _identity(op, vals.dtype)
    v = _pad_axis(vals, RB, 0, ident)
    s = _pad_axis(seg_ids.astype(jnp.int32), RB, 0, -1)
    nb = v.shape[0] // RB
    ts = min(TS, num_segments + (-num_segments) % SUB)
    sp = num_segments + (-num_segments) % ts
    out = pl.pallas_call(
        functools.partial(_segment_kernel, op=op),
        grid=(sp // ts, nb),
        in_specs=[
            pl.BlockSpec((None, 1, RB), lambda s_, r: (r, 0, 0)),
            pl.BlockSpec((None, 1, RB), lambda s_, r: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((ts, LANE), lambda s_, r: (s_, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, LANE), vals.dtype),
        interpret=interpret,
    )(v.reshape(nb, 1, RB), s.reshape(nb, 1, RB))
    return _REDUCE[op](out, axis=1)[:num_segments]
