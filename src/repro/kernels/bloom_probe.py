"""Pallas TPU kernel: bloom-filter probe (QUIP join trigger / semi-join filter).

This is the probe used by BF_Join (paper Alg. 2) and the VF-list semi-join
filter (paper §5.3).  Keys arrive pre-folded to uint32 (``hashing.fold64``);
each lane computes ``num_hashes`` multiply-shift bit positions and tests
them against the bitset.

Mosaic has no vector gather from a VMEM array, so the word lookup is a
one-hot selection on the MXU.  The bitset is laid out as ``Rp`` rows of
128 words, and each word is split into its four bytes, giving a
``(4·128, Rp)`` bf16 plane matrix (bytes 0..255 are exact in bf16).  For a
block of keys held lane-dense as ``(1, BLOCK)``:

* ``onehot (Rp, BLOCK)`` marks each key's row, and
  ``planes @ onehot → (512, BLOCK)`` copies that row's bytes into the key's
  column.  Each output has exactly one non-zero product, so the f32
  accumulation is exact;
* a compare against a sublane iota picks the byte at
  ``(bit >> 3)·128 + lane``, and ``(byte >> (bit & 7)) & 1`` is the bit.

Hash arithmetic runs in int32 with logical shifts (the same bits as the
uint32 wraparound math of ``hashing.hash_positions_np``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.hashing import MULTIPLIERS, OFFSETS

__all__ = ["bloom_probe_pallas"]

BLOCK = 512  # keys per grid step (lanes)
LANE = 128  # words per bitset row
ROWS = 128  # bitset rows are padded to a multiple of this (MXU contraction)


def _i32(u: int) -> jnp.int32:
    """A uint32 constant as the int32 with the same bits."""
    return jnp.int32(u - (1 << 32) if u >= 1 << 31 else u)


def _srl(x, n: int):
    return jax.lax.shift_right_logical(x, jnp.int32(n))


def _kernel(folded_ref, planes_ref, out_ref, *, num_hashes: int, log2m: int):
    folded = folded_ref[...]  # (1, BLOCK) int32 bit patterns
    planes = planes_ref[...]  # (4*LANE, Rp) bf16 byte planes
    rp = planes.shape[1]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (rp, BLOCK), 0)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (4 * LANE, BLOCK), 0)
    ok = jnp.ones(folded.shape, dtype=jnp.int32)
    for i in range(num_hashes):
        h = folded * _i32(int(MULTIPLIERS[i])) + _i32(int(OFFSETS[i]))
        pos = _srl(h, 32 - log2m)
        word = _srl(pos, 5)
        bit = pos & 31
        onehot = (row_iota == _srl(word, 7)).astype(jnp.bfloat16)
        sel = jax.lax.dot_general(
            planes, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (4*LANE, BLOCK): the key's row, byte-split
        col = _srl(bit, 3) * LANE + (word & (LANE - 1))
        byte = jnp.sum(jnp.where(col_iota == col, sel, 0.0), axis=0,
                       keepdims=True).astype(jnp.int32)
        ok = ok & (byte >> (bit & 7)) & 1
    out_ref[...] = ok


def _byte_planes(bits: jnp.ndarray) -> jnp.ndarray:
    """(W,) uint32 bitset → (4·LANE, Rp) bf16: ``[j·LANE + l, r]`` is byte
    ``j`` of word ``r·LANE + l``; padding words are zero."""
    w = bits.astype(jnp.uint32)
    rows = -(-w.shape[0] // LANE)
    rp = rows + (-rows) % ROWS
    w = jnp.pad(w, (0, rp * LANE - w.shape[0])).reshape(rp, LANE)
    shifts = jnp.arange(4, dtype=jnp.uint32)[:, None, None] * jnp.uint32(8)
    planes = (w[None] >> shifts) & jnp.uint32(255)  # (4, Rp, LANE)
    planes = planes.transpose(0, 2, 1).reshape(4 * LANE, rp)
    return planes.astype(jnp.float32).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("num_hashes", "log2m", "interpret"))
def bloom_probe_pallas(
    bits: jnp.ndarray,
    folded: jnp.ndarray,
    *,
    num_hashes: int,
    log2m: int,
    interpret: bool,
) -> jnp.ndarray:
    """bits: (2**log2m // 32,) uint32; folded: (n,) uint32 keys → (n,) bool.

    Keys are pre-folded to uint32 on the host (``hashing.fold64``): x32-mode
    JAX and the TPU VPU have no 64-bit integer lanes.
    """
    n = folded.shape[0]
    f = jax.lax.bitcast_convert_type(folded.astype(jnp.uint32), jnp.int32)
    f = jnp.pad(f, (0, (-n) % BLOCK))
    nb = f.shape[0] // BLOCK
    planes = _byte_planes(bits)
    out = pl.pallas_call(
        functools.partial(_kernel, num_hashes=num_hashes, log2m=log2m),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((None, 1, BLOCK), lambda i: (i, 0, 0)),
            pl.BlockSpec(planes.shape, lambda i: (0, 0)),  # VMEM-resident
        ],
        out_specs=pl.BlockSpec((None, 1, BLOCK), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, BLOCK), jnp.int32),
        interpret=interpret,
    )(f.reshape(nb, 1, BLOCK), planes)
    return out.reshape(-1)[:n] == 1
