"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: kernels are validated against these in
``tests/test_kernels.py`` over shape/dtype sweeps (interpret=True on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.hashing import MULTIPLIERS, OFFSETS

__all__ = [
    "bloom_probe_ref",
    "hash_join_probe_sorted_ref",
    "hash_join_ref",
    "masked_distance_ref",
    "masked_knn_ref",
    "neighbor_mean_ref",
    "neighbor_mode_ref",
    "segment_reduce_ref",
]


def hash_join_probe_sorted_ref(
    sorted_keys: jnp.ndarray, order: jnp.ndarray, probe_folded: jnp.ndarray,
    max_dup: int,
):
    """Probe half of the sort-based join: build side pre-sorted once
    (``sorted_keys = build[order]``, stable) so chunked probes don't repeat
    the O(nb·log nb) sort.  Returns ``(counts (np,) int32,
    matches (np, max_dup) int32)``: row i holds the build rows whose folded
    key equals probe i's, ascending, ``-1``-padded."""
    nb = sorted_keys.shape[0]
    lo = jnp.searchsorted(sorted_keys, probe_folded, side="left")
    hi = jnp.searchsorted(sorted_keys, probe_folded, side="right")
    counts = (hi - lo).astype(jnp.int32)
    j = jnp.arange(max_dup, dtype=jnp.int32)
    idx = lo.astype(jnp.int32)[:, None] + j[None, :]
    valid = j[None, :] < counts[:, None]
    gathered = jnp.take(
        order, jnp.clip(idx, 0, max(nb - 1, 0)), axis=0
    ).astype(jnp.int32)
    return counts, jnp.where(valid, gathered, -1)


def hash_join_ref(
    build_folded: jnp.ndarray, probe_folded: jnp.ndarray, max_dup: int
):
    """Fold-level hash-join candidates, sort-based (the jnp oracle for the
    open-addressing Pallas pair in ``hash_join.py``).

    build_folded: (nb,) uint32; probe_folded: (np,) uint32; ``max_dup`` is a
    static bound on the fold-level duplication of the build side.  Fold
    collisions are resolved by the host wrapper (``ops.hash_join_match``)
    against the original 64-bit keys.
    """
    order = jnp.argsort(build_folded, stable=True).astype(jnp.int32)
    return hash_join_probe_sorted_ref(
        build_folded[order], order, probe_folded, max_dup
    )

def bloom_probe_ref(
    bits: jnp.ndarray, folded: jnp.ndarray, num_hashes: int, log2m: int
) -> jnp.ndarray:
    """bits: (2**log2m // 32,) uint32 bitset.  folded: (n,) uint32 keys
    (int64 keys are folded on the host — see ``hashing.fold64`` — because
    x32-mode JAX has no 64-bit lanes).  True iff all ``num_hashes`` bits
    are set."""
    folded = folded.astype(jnp.uint32)[:, None]
    a = jnp.asarray(MULTIPLIERS[:num_hashes])[None, :]
    b = jnp.asarray(OFFSETS[:num_hashes])[None, :]
    pos = ((folded * a + b) >> jnp.uint32(32 - log2m)).astype(jnp.uint32)
    word = (pos >> jnp.uint32(5)).astype(jnp.int32)
    bit = pos & jnp.uint32(31)
    w = jnp.take(bits, word, axis=0)
    hit = (w >> bit) & jnp.uint32(1)
    return jnp.all(hit == 1, axis=1)


def masked_distance_ref(
    q: jnp.ndarray, qm: jnp.ndarray, r: jnp.ndarray, rm: jnp.ndarray
) -> jnp.ndarray:
    """Partial-distance matrix for masked KNN (sklearn KNNImputer semantics).

    q: (nq, d) float32, qm: (nq, d) observed-mask (1.0 observed, 0.0 missing)
    r: (nr, d), rm: (nr, d).
    dist[i,j] = (d / n_co) * sum_k qm*rm*(q-r)^2   over co-observed dims;
    +inf (large) where n_co == 0.
    """
    q = q.astype(jnp.float32) * qm
    r = r.astype(jnp.float32) * rm
    # full f32 matmuls (a TPU's default f32 precision is one bf16 pass)
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    q2 = mm(q * q, rm.T)  # sum_k qm*q^2*rm  (qm baked into q)
    r2 = mm(qm, (r * r).T)
    cross = mm(q, r.T)
    sq = q2 + r2 - 2.0 * cross
    n_co = mm(qm, rm.T)
    d = q.shape[1]
    scaled = jnp.where(n_co > 0, sq * (d / jnp.maximum(n_co, 1.0)), jnp.inf)
    return jnp.maximum(scaled, 0.0)


def masked_knn_ref(
    q: jnp.ndarray,
    qm: jnp.ndarray,
    r: jnp.ndarray,
    rm: jnp.ndarray,
    k: int,
):
    """Top-k smallest partial distances.  Returns (dists (nq,k), idx (nq,k))."""
    dmat = masked_distance_ref(q, qm, r, rm)
    neg, idx = jax.lax.top_k(-dmat, k)
    return -neg, idx


def neighbor_mean_ref(vals: jnp.ndarray) -> jnp.ndarray:
    """KNN float aggregation: per-row mean of the (b, k) neighbour targets."""
    return vals.astype(jnp.float32).mean(axis=1)


def neighbor_mode_ref(codes: jnp.ndarray, num_classes: int) -> jnp.ndarray:
    """KNN categorical aggregation: per-row mode over dictionary codes.

    codes: (b, k) int32 in [0, num_classes).  Returns (b,) int32 — the class
    with the highest count; ties break to the *smallest* class index
    (``jnp.argmax`` returns the first maximum), which, with classes produced
    by ``np.unique`` (ascending values), matches the per-row
    ``u[np.argmax(c)]`` loop of the seed imputer bit-for-bit.
    """
    onehot = jax.nn.one_hot(codes, num_classes, dtype=jnp.int32)  # (b, k, U)
    counts = onehot.sum(axis=1)  # (b, U)
    return jnp.argmax(counts, axis=1).astype(jnp.int32)


def segment_reduce_ref(
    vals: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int, op: str
) -> jnp.ndarray:
    """Grouped-aggregate segment reduction (the jnp oracle for
    ``segment_ops.segment_reduce_pallas``).

    vals: (n,); seg_ids: (n,) int32 in [0, num_segments) (negative ids drop
    the row).  ``op`` is static: sum/min/max — count is a sum of ones, done
    by the caller.  Empty segments hold the reduction identity of the
    compute dtype (0 / dtype-max / dtype-min), matching ``jax.ops``
    semantics; callers mask them via the count op.
    """
    if op == "sum":
        return jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments)
    if op == "min":
        return jax.ops.segment_min(vals, seg_ids, num_segments=num_segments)
    if op == "max":
        return jax.ops.segment_max(vals, seg_ids, num_segments=num_segments)
    raise ValueError(f"unknown segment op {op!r}")

