"""Pallas kernel pair: open-addressing hash-join build + probe.

This is the kernel backing of the QUIP join spine (modified outer join ⋈̂,
paper Alg. 1, and the BF_Join recovery pass, Alg. 2).  The relational core —
"all (probe_idx, build_idx) pairs with equal keys" — was previously served
by a pure-NumPy sort-join (``core.triggers.multi_match``); these kernels move
it onto the same ref/pallas dispatch layer as the bloom probe and the masked
KNN distance (``kernels.ops``).

Layout
------
Keys are host-folded int64 → uint32 (``hashing.fold64``) because x32-mode JAX
and the TPU VPU have no 64-bit integer lanes.  Fold collisions therefore make
the kernel emit *candidate* pairs; the ``ops.hash_join_match`` wrapper
re-checks candidates against the original 64-bit keys on the host, so the
subsystem is exact end-to-end.

* **build** — one sequential pass inserting each build key into a
  power-of-two open-addressing table (linear probing, multiply-shift home
  slot).  Slots store the folded key plus the build-row index; ``idx == -1``
  marks an empty slot, so any uint32 key value is representable.  Insertion
  in row order makes fold-equal keys occupy their shared probe chain in
  ascending row order — exactly the order the sort-based NumPy oracle emits.
* **probe** — a grid over ``BLOCK``-lane probe-key blocks with the whole
  table VMEM-resident (like the bloom-probe bitset).  Each lane walks its
  chain until the first empty slot, counting matches and scattering matched
  build indices into a fixed-size ``(BLOCK, max_dup)`` match block via a
  one-hot column select (``max_dup`` = max fold-level duplication of the
  build side, a static host-computed bound).  Outputs are the per-probe match
  counts plus the ragged pairs in these fixed-size blocks.

Chain walks terminate because the table is at most half full (capacity ≥ 2n),
and a defensive step bound of ``capacity`` caps the while loop regardless.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "hash_join_build_pallas",
    "hash_join_probe_pallas",
    "table_log2cap",
]

BLOCK = 256  # probe keys per grid step

# Dedicated odd multiplier for the table's home-slot hash (splitmix-derived,
# distinct from the bloom filter's MULTIPLIERS so table layout and bloom bits
# stay uncorrelated).
_TABLE_MULT = 0x2545F491


def table_log2cap(n_build: int) -> int:
    """log2 table capacity: smallest power of two ≥ 2·n (load factor ≤ 0.5),
    floored at 128 slots so tiny builds still vectorize."""
    cap = 128
    log2cap = 7
    while cap < 2 * max(n_build, 1):
        cap <<= 1
        log2cap += 1
    return log2cap


def _home(keys: jnp.ndarray, log2cap: int) -> jnp.ndarray:
    return (keys * jnp.uint32(_TABLE_MULT)) >> jnp.uint32(32 - log2cap)


# --------------------------------------------------------------------------- #
# build
# --------------------------------------------------------------------------- #
def _build_kernel(keys_ref, slot_key_ref, slot_idx_ref, *, n: int,
                  log2cap: int):
    # The table is carried functionally through the insertion loop (ref
    # reads inside a while_loop cond don't discharge in interpret mode) and
    # written back once at the end.
    cap = 1 << log2cap
    mask = jnp.uint32(cap - 1)
    keys = keys_ref[...].astype(jnp.uint32)

    def insert(i, table):
        slot_key, slot_idx = table
        key = keys[i]

        def occupied(pos):
            return (
                jax.lax.dynamic_index_in_dim(
                    slot_idx, pos.astype(jnp.int32), keepdims=False
                )
                >= 0
            )

        pos = jax.lax.while_loop(
            occupied, lambda p: (p + 1) & mask, _home(key, log2cap)
        )
        at = pos.astype(jnp.int32)
        return (
            jax.lax.dynamic_update_index_in_dim(slot_key, key, at, 0),
            jax.lax.dynamic_update_index_in_dim(
                slot_idx, i.astype(jnp.int32), at, 0
            ),
        )

    slot_key, slot_idx = jax.lax.fori_loop(
        0,
        n,
        insert,
        (jnp.zeros((cap,), jnp.uint32), jnp.full((cap,), -1, jnp.int32)),
    )
    slot_key_ref[...] = slot_key
    slot_idx_ref[...] = slot_idx


@functools.partial(jax.jit, static_argnames=("log2cap", "interpret"))
def hash_join_build_pallas(
    folded: jnp.ndarray, *, log2cap: int, interpret: bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """folded: (n,) uint32 build keys → (slot_key (cap,) uint32,
    slot_idx (cap,) int32) with ``slot_idx == -1`` marking empty slots."""
    n = folded.shape[0]
    cap = 1 << log2cap
    assert cap >= 2 * max(n, 1), "hash table must stay at most half full"
    return pl.pallas_call(
        functools.partial(_build_kernel, n=n, log2cap=log2cap),
        grid=(1,),
        in_specs=[pl.BlockSpec((n,), lambda i: (0,))],
        out_specs=[
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((cap,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap,), jnp.uint32),
            jax.ShapeDtypeStruct((cap,), jnp.int32),
        ],
        interpret=interpret,
    )(folded.astype(jnp.uint32))


# --------------------------------------------------------------------------- #
# probe
# --------------------------------------------------------------------------- #
def _probe_kernel(probe_ref, slot_key_ref, slot_idx_ref, counts_ref,
                  matches_ref, *, log2cap: int, max_dup: int):
    cap = 1 << log2cap
    mask = jnp.uint32(cap - 1)
    keys = probe_ref[...].astype(jnp.uint32)
    slot_key = slot_key_ref[...]
    slot_idx = slot_idx_ref[...]
    nlanes = keys.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (nlanes, max_dup), 1)

    def cond(state):
        _pos, _cnt, _m, active, step = state
        return jnp.logical_and(jnp.any(active), step < cap)

    def body(state):
        pos, cnt, m, active, step = state
        at = pos.astype(jnp.int32)
        sk = jnp.take(slot_key, at, axis=0)
        si = jnp.take(slot_idx, at, axis=0)
        occupied = si >= 0
        match = active & occupied & (sk == keys)
        put = match[:, None] & (col == jnp.minimum(cnt, max_dup - 1)[:, None])
        m = jnp.where(put, si[:, None], m)
        cnt = cnt + match.astype(jnp.int32)
        active = active & occupied
        pos = jnp.where(active, (pos + 1) & mask, pos)
        return pos, cnt, m, active, step + 1

    state = (
        _home(keys, log2cap),
        jnp.zeros(nlanes, jnp.int32),
        jnp.full((nlanes, max_dup), -1, jnp.int32),
        jnp.ones(nlanes, jnp.bool_),
        jnp.int32(0),
    )
    _pos, cnt, m, _active, _step = jax.lax.while_loop(cond, body, state)
    counts_ref[...] = cnt
    matches_ref[...] = m


@functools.partial(
    jax.jit, static_argnames=("log2cap", "max_dup", "interpret")
)
def hash_join_probe_pallas(
    slot_key: jnp.ndarray,
    slot_idx: jnp.ndarray,
    folded_probe: jnp.ndarray,
    *,
    log2cap: int,
    max_dup: int,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Probe the built table with (n,) uint32 keys.

    Returns ``(counts (n,) int32, matches (n, max_dup) int32)`` where row i
    holds the matched build-row indices in chain order (ascending build row
    for fold-equal keys) and ``-1`` pads unused columns.
    """
    n = folded_probe.shape[0]
    cap = 1 << log2cap
    f = folded_probe.astype(jnp.uint32)
    pad = (-n) % BLOCK
    if pad:
        f = jnp.pad(f, (0, pad))
    npad = f.shape[0]
    counts, matches = pl.pallas_call(
        functools.partial(_probe_kernel, log2cap=log2cap, max_dup=max_dup),
        grid=(npad // BLOCK,),
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((cap,), lambda i: (0,)),  # whole table in VMEM
            pl.BlockSpec((cap,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK, max_dup), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad,), jnp.int32),
            jax.ShapeDtypeStruct((npad, max_dup), jnp.int32),
        ],
        interpret=interpret,
    )(f, slot_key, slot_idx)
    return counts[:n], matches[:n]
