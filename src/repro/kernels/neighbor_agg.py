"""Pallas TPU kernels: KNN neighbour aggregation (mean / categorical mode).

After the masked-distance kernel and top-k pick the k neighbours per query
row, the imputed value is a per-row reduction of the gathered neighbour
targets — a float mean, or, for dictionary-coded categorical attributes, the
mode.  The seed engine ran the mode as a per-row Python loop
(``np.unique`` + ``argmax`` per row), an O(b·k) interpreter hot path inside
the paper's dominant cost (Fig. 2: KNN inference).  Here both reductions are
single-pass vector kernels.

Both kernels take the neighbour matrix transposed, ``(k, b)``: query rows
run along the 128-wide lane axis and the k neighbours along sublanes, so
every block and every output is lane-dense (a 1-D ``(b,)`` block does not
match the TPU's tiled layout).

* ``neighbor_mean_pallas``  — (b, k) float32 → (b,) row means.  Padded
  neighbour rows are zero, so the sum is exact and the divide uses the
  true k.
* ``neighbor_mode_pallas``  — (b, k) int32 dictionary codes → (b,) argmax
  of the per-row bincount.  Classes are swept in chunks of ``CB`` sublanes:
  each chunk counts its classes in f32 (one compare+add per neighbour row —
  k is small and static, so the loop unrolls), takes the chunk maximum and
  the smallest class reaching it, and replaces the running best only on a
  strictly larger count.  Ties therefore break to the smallest class index,
  matching the ``np.unique``-order semantics of the NumPy oracle
  bit-for-bit, and VMEM use is independent of the class count.

Padded codes are −1, which matches no class; fully-padded rows resolve to
class 0 and are sliced off by the host wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["neighbor_mean_pallas", "neighbor_mode_pallas"]

BB = 128  # query rows per block (lanes)
SUB = 8  # sublane multiple for the k axis
CB = 256  # classes per sweep chunk (sublanes)


def _pad_axis(x, mult, axis, value):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _mean_kernel(vals_ref, out_ref, *, k: int):
    vals = vals_ref[...]  # (Kp, BB); pad rows are 0
    out_ref[...] = jnp.sum(vals, axis=0, keepdims=True) / jnp.float32(k)


@functools.partial(jax.jit, static_argnames=("interpret",))
def neighbor_mean_pallas(vals: jnp.ndarray, *, interpret: bool
                         ) -> jnp.ndarray:
    """(b, k) float32 neighbour targets → (b,) float32 row means."""
    b, k = vals.shape
    v = _pad_axis(vals.astype(jnp.float32).T, SUB, 0, 0.0)
    v = _pad_axis(v, BB, 1, 0.0)
    kp, bp = v.shape
    out = pl.pallas_call(
        functools.partial(_mean_kernel, k=k),
        grid=(bp // BB,),
        in_specs=[pl.BlockSpec((kp, BB), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, BB), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, bp), jnp.float32),
        interpret=interpret,
    )(v)
    return out[0, :b]


def _mode_kernel(codes_ref, out_ref, *, k: int, num_chunks: int):
    codes = codes_ref[...]  # (Kp, BB) int32; pad entries are -1
    iota = jax.lax.broadcasted_iota(jnp.int32, (CB, BB), 0)

    def chunk(c, carry):
        best_n, best_c = carry
        classes = iota + c * CB
        counts = jnp.zeros((CB, BB), jnp.float32)
        for j in range(k):  # static unroll: KNN k is small
            counts = counts + (codes[j:j + 1, :] == classes).astype(
                jnp.float32)
        top = jnp.max(counts, axis=0, keepdims=True)
        first = jnp.min(
            jnp.where(counts == top, classes.astype(jnp.float32),
                      jnp.float32(jnp.inf)),
            axis=0, keepdims=True,
        )
        better = top > best_n  # strict: earlier (smaller) classes keep ties
        return (jnp.where(better, top, best_n),
                jnp.where(better, first, best_c))

    init = (jnp.full((1, BB), -1.0, jnp.float32),
            jnp.zeros((1, BB), jnp.float32))
    _n, best = jax.lax.fori_loop(0, num_chunks, chunk, init)
    out_ref[...] = best.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_classes", "interpret"))
def neighbor_mode_pallas(codes: jnp.ndarray, *, num_classes: int,
                         interpret: bool) -> jnp.ndarray:
    """(b, k) int32 codes in [0, num_classes) → (b,) int32 per-row mode
    class (bincount argmax, ties to the smallest class index)."""
    b, k = codes.shape
    c = _pad_axis(codes.astype(jnp.int32).T, SUB, 0, -1)
    c = _pad_axis(c, BB, 1, -1)
    kp, bp = c.shape
    out = pl.pallas_call(
        functools.partial(_mode_kernel, k=k,
                          num_chunks=max(1, -(-num_classes // CB))),
        grid=(bp // BB,),
        in_specs=[pl.BlockSpec((kp, BB), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, BB), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, bp), jnp.int32),
        interpret=interpret,
    )(c)
    return out[0, :b]
