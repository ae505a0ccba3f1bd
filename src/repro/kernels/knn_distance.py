"""Pallas TPU kernel: masked partial-distance matrix for KNN imputation.

This is the imputation hot spot the paper optimizes against (KNN inference
dominates query time in Fig. 2/9/10).  The masked L2 distance decomposes into
three MXU matmuls (see ``ref.masked_distance_ref``):

    dist = (q²·qm) @ rmᵀ + qm @ (r²·rm)ᵀ − 2·(q·qm) @ (r·rm)ᵀ
    n_co = qm @ rmᵀ

so the kernel tiles (nq, nr) into MXU-aligned (BQ=128, BR=128) output blocks
with the feature dimension streamed in VMEM-resident (BK) chunks and all four
accumulations fused into a single pass (one read of q/r per tile instead of
four — 4× HBM traffic saving over composing the ref einsums).

Grid: (nq/BQ, nr/BR, d/BK); the k-loop accumulates into the output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["masked_distance_pallas"]

BQ, BR, BK = 128, 128, 128


def _dot_t(a, b):
    """``a @ b.T`` in full f32: the MXU's default f32 precision is a single
    bf16 pass, too coarse to rank neighbours as the f32 oracle does."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _kernel(q_ref, qm_ref, r_ref, rm_ref, out_ref, *, d_total: int, nk: int):
    kidx = pl.program_id(2)

    q = q_ref[...].astype(jnp.float32)
    qm = qm_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    rm = rm_ref[...].astype(jnp.float32)

    qv = q * qm
    rv = r * rm

    # Fused partial sums for this feature chunk.
    q2 = _dot_t(qv * qv, rm)
    r2 = _dot_t(qm, rv * rv)
    cross = _dot_t(qv, rv)
    nco = _dot_t(qm, rm)

    sq = q2 + r2 - 2.0 * cross

    @pl.when(kidx == 0)
    def _init():
        out_ref[0, ...] = sq
        out_ref[1, ...] = nco

    @pl.when(kidx > 0)
    def _acc():
        out_ref[0, ...] += sq
        out_ref[1, ...] += nco

    # Final chunk: rescale by d/n_co and mark empty overlaps unreachable.
    @pl.when(kidx == nk - 1)
    def _finalize():
        acc_sq = out_ref[0, ...]
        acc_n = out_ref[1, ...]
        scaled = jnp.where(
            acc_n > 0.0,
            jnp.maximum(acc_sq, 0.0) * (d_total / jnp.maximum(acc_n, 1.0)),
            jnp.float32(jnp.inf),
        )
        out_ref[0, ...] = scaled


def _pad_to(x, mult, axis, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_distance_pallas(q, qm, r, rm, *, interpret: bool):
    """(nq, d) x (nr, d) → (nq, nr) scaled partial distances (float32)."""
    nq, d = q.shape
    nr = r.shape[0]
    q = _pad_to(q.astype(jnp.float32), BQ, 0)
    qm = _pad_to(qm.astype(jnp.float32), BQ, 0)
    r = _pad_to(r.astype(jnp.float32), BR, 0)
    rm = _pad_to(rm.astype(jnp.float32), BR, 0)
    q = _pad_to(q, BK, 1)
    qm = _pad_to(qm, BK, 1)
    r = _pad_to(r, BK, 1)
    rm = _pad_to(rm, BK, 1)
    nqp, dp = q.shape
    nrp = r.shape[0]
    nk = dp // BK

    grid = (nqp // BQ, nrp // BR, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, d_total=d, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BQ, BK), lambda i, j, k: (i, k)),
            pl.BlockSpec((BQ, BK), lambda i, j, k: (i, k)),
            pl.BlockSpec((BR, BK), lambda i, j, k: (j, k)),
            pl.BlockSpec((BR, BK), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((2, BQ, BR), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((2, nqp, nrp), jnp.float32),
        interpret=interpret,
    )(q, qm, r, rm)
    return out[0, :nq, :nr]
